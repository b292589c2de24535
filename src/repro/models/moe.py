"""Mixture-of-Experts FFN: GShard-style grouped, index-based dispatch.

Formulation (the TPU-native one — GShard/Switch):

* tokens are split into **G groups**, G = number of batch-axis shards, so all
  routing bookkeeping (top-k, position-in-expert cumsum, capacity dropping)
  is *local to a data shard* — no cross-shard scatter;
* capacity is per group, ``Cg = cf · tokens_per_group · K / E``;
* dispatch is by **indices** (scatter-add into a (G, E·Cg, D) buffer), not by
  the (tokens × E × C) one-hot einsum — at olmoe/grok scale the one-hot
  tensor is tens of GB;
* expert compute is ``einsum('gecd,edf->gecf')`` with G on the batch axes and
  E on "model" (expert parallelism): the only communication is the reshard
  of the dispatch buffer along E — the all-to-all of classical EP.  When E
  does not divide the model axis (grok-1: 8 experts, 16-way axis), experts
  stay replicated and the expert *hidden* dim is tensor-parallel instead.

Router: softmax → top-k, renormalized; dropped tokens (beyond capacity)
contribute zero — standard Switch semantics.  Returns a load-balance aux
loss (Switch: E · Σ_e f_e·p_e, averaged over groups).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distrib.act import batch_shards, current_binding, shard

from .layers import activation


def _local_dispatch(xt, probs, E, K, C, dtype):
    """Local (single-shard) top-k routing + index dispatch bookkeeping.
    Returns (gate (t,K), keep (t·K,), dest (t·K,) with E·C = scratch)."""
    gate, idx = jax.lax.top_k(probs, K)  # (t, K)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    flat_e = idx.reshape(-1)
    oh = jax.nn.one_hot(flat_e, E, dtype=jnp.float32)
    pos = jnp.cumsum(oh, axis=0) - oh
    slot = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    keep = (slot < C).astype(dtype)
    dest = (flat_e * C + slot.astype(jnp.int32)).astype(jnp.int32)
    dest = jnp.where(keep > 0, dest, E * C)
    return gate, idx, keep, dest


def moe_ffn(
    params,
    x: jax.Array,  # (b, s, D)
    cfg,
    *,
    capacity_factor: Optional[float] = None,
    groups: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (out (b,s,D), aux_loss scalar)."""
    b, s, Dm = x.shape
    E = cfg.num_experts
    K = cfg.num_experts_per_tok
    cf = capacity_factor if capacity_factor is not None else cfg.capacity_factor
    t = b * s
    G = groups if groups is not None else batch_shards()
    if t % G != 0 or (t // G) < E // K:
        G = 1
    tg = t // G
    Cg = max(1, int(cf * tg * K / E))

    xg = x.reshape(G, tg, Dm)
    logits = jnp.einsum("gtd,de->gte", xg, params["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, K)  # (G, tg, K)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # load-balance aux loss (per group, then averaged)
    me = probs.mean(axis=1)  # (G, E)
    ce = jnp.zeros((G, E), jnp.float32)
    g_idx = jnp.arange(G)[:, None, None]
    ce = ce.at[jnp.broadcast_to(g_idx, idx.shape), idx].add(1.0) / (tg * K)
    aux = E * jnp.mean(jnp.sum(me * ce, axis=-1))

    # position-in-expert within each group (token-major over tg·K slots)
    flat_e = idx.reshape(G, tg * K)
    oh = jax.nn.one_hot(flat_e, E, dtype=jnp.float32)  # (G, tgK, E)
    pos = jnp.cumsum(oh, axis=1) - oh
    slot = jnp.take_along_axis(pos, flat_e[..., None], axis=2)[..., 0]  # (G, tgK)
    keep = (slot < Cg).astype(x.dtype)
    dest = (flat_e * Cg + slot.astype(jnp.int32)).astype(jnp.int32)
    dest = jnp.where(keep > 0, dest, E * Cg)  # dropped → scratch row

    x_rep = jnp.repeat(xg, K, axis=1)  # (G, tgK, D)
    buf = jnp.zeros((G, E * Cg + 1, Dm), x.dtype)
    buf = buf.at[jnp.arange(G)[:, None], dest].add(x_rep * keep[..., None])
    # the EP reshard: G stays on the batch axes, E moves to "model"
    expert_in = shard(buf[:, : E * Cg].reshape(G, E, Cg, Dm),
                      "moe_group", "experts", None, None)

    hmid = jnp.einsum("gecd,edf->gecf", expert_in, params["w_in"])
    hmid = shard(hmid, "moe_group", "experts", None, "moe_ffn")
    if cfg.mlp_gated:
        g = jnp.einsum("gecd,edf->gecf", expert_in, params["w_gate"])
        hmid = activation(g, cfg.hidden_act) * hmid
    else:
        hmid = activation(hmid, cfg.hidden_act)
    expert_out = shard(jnp.einsum("gecf,efd->gecd", hmid, params["w_out"]),
                       "moe_group", "experts", None, None)  # (G,E,Cg,D)

    out_flat = expert_out.reshape(G, E * Cg, Dm)
    out_pad = jnp.concatenate(
        [out_flat, jnp.zeros((G, 1, Dm), out_flat.dtype)], axis=1
    )
    gathered = out_pad[jnp.arange(G)[:, None], dest]  # (G, tgK, D)
    w = gate.reshape(G, tg * K).astype(jnp.float32) * keep.astype(jnp.float32)
    y = (gathered.astype(jnp.float32) * w[..., None]).reshape(G, tg, K, Dm).sum(axis=2)
    y = shard(y.reshape(b, s, Dm).astype(x.dtype), "batch", "seq", "embed")
    return y, aux


# ---------------------------------------------------------------------------
# shard_map expert parallelism (the distributed hot path)
# ---------------------------------------------------------------------------
#
# Under pure GSPMD the index-based dispatch gets pessimized: the partitioner
# cannot prove the scatter/gather stay shard-local and inserts full-size
# all-reduces of the (tokens·K, D) tensors (measured: 15.6 TB wire per step
# for olmoe-1b-7b).  The explicit formulation below makes the communication
# pattern exact:
#
# * activations are batch-sharded; every model shard holds the same local
#   tokens, so *dispatch needs no communication at all*: shard j simply
#   selects the tokens routed to the experts it owns (EP) or computes every
#   expert on its slice of the hidden dim (TP, when E < model-axis);
# * the only collective is one psum over "model" of the combined output —
#   identical in shape to the dense-FFN TP all-reduce;
# * FSDP-sharded expert weights are all-gathered over the batch axes right
#   before use, exactly like the dense path's GSPMD-inserted gathers.

def moe_ffn_sharded(
    params,
    x: jax.Array,  # (b, s, D)
    cfg,
    *,
    capacity_factor: Optional[float] = None,
) -> Tuple[jax.Array, jax.Array]:
    bound = current_binding()
    assert bound is not None
    mesh, rules = bound
    b, s, Dm = x.shape
    batch_axes = rules.get("batch") or ()
    if isinstance(batch_axes, str):
        batch_axes = (batch_axes,)
    shards = 1
    for a in batch_axes:
        shards *= mesh.shape[a]
    if not batch_axes or b % shards != 0 or "model" not in mesh.shape:
        return moe_ffn(params, x, cfg, capacity_factor=capacity_factor, groups=1)

    E = cfg.num_experts
    K = cfg.num_experts_per_tok
    cf = capacity_factor if capacity_factor is not None else cfg.capacity_factor
    msize = mesh.shape["model"]
    ep = E % msize == 0
    gated = cfg.mlp_gated
    P_ = jax.sharding.PartitionSpec
    fsdp = rules.get("moe_weight_fsdp")
    if isinstance(fsdp, str):
        fsdp = (fsdp,)
    fsdp = fsdp or ()

    if ep:
        w_in_spec = P_("model", fsdp, None)   # (E, D, F)
        w_out_spec = P_("model", None, fsdp)  # (E, F, D)
    else:
        w_in_spec = P_(None, fsdp, "model")
        w_out_spec = P_(None, "model", fsdp)
    x_spec = P_(fsdp, None, None)
    r_spec = P_(None, None)

    quant = bool(getattr(cfg, "moe_int8_gather", False)) and bool(fsdp)

    def _gather_fsdp(w, axis):
        """FSDP weight gather; optionally int8-quantized on the wire
        (§Perf cell B): per-row symmetric scales ride along (<1% payload),
        dequantized after the gather. Halves gather bytes vs bf16."""
        if not fsdp:
            return w  # serving (TP-only) layout: no-op
        if not quant:
            for a in reversed(fsdp):
                w = jax.lax.all_gather(w, a, axis=axis, tiled=True)
            return w
        # scale axis must NOT be the gathered axis (scales concatenate
        # alongside their int8 blocks)
        red = w.ndim - 1 if axis != w.ndim - 1 else w.ndim - 2
        scale = jnp.max(jnp.abs(w), axis=red, keepdims=True).astype(jnp.float32)
        scale = scale / 127.0 + 1e-12
        q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -127, 127
                     ).astype(jnp.int8)
        for a in reversed(fsdp):
            q = jax.lax.all_gather(q, a, axis=axis, tiled=True)
            scale = jax.lax.all_gather(scale, a, axis=axis, tiled=True)
        return (q.astype(jnp.float32) * scale).astype(w.dtype)

    def inner(xl, router, w_in, w_gate, w_out):
        b_loc = xl.shape[0]
        t_loc = b_loc * s
        xt = xl.reshape(t_loc, Dm)
        logits = jnp.einsum("td,de->te", xt, router).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        C = max(1, int(cf * t_loc * K / E))
        gate, idx, keep, dest = _local_dispatch(xt, probs, E, K, C, xt.dtype)
        x_rep = jnp.repeat(xt, K, axis=0)
        keepf = keep.astype(jnp.float32)

        # aux loss (identical across model shards; mean over data shards)
        me = probs.mean(axis=0)
        ce = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0) / (t_loc * K)
        aux = E * jnp.sum(me * ce)
        for a in fsdp:
            aux = jax.lax.pmean(aux, a)

        if ep:
            E_loc = E // msize
            j = jax.lax.axis_index("model")
            w_in_g = _gather_fsdp(w_in, 1)
            w_gate_g = _gather_fsdp(w_gate, 1) if gated else None
            w_out_g = _gather_fsdp(w_out, 2)
            own = ((dest // C) // E_loc) == j  # scratch row → E//E_loc ≥ msize → False
            dest_loc = jnp.where(own, dest - j * (E_loc * C), E_loc * C)
            wts = keep * own.astype(keep.dtype)
            buf = jnp.zeros((E_loc * C + 1, Dm), xt.dtype)
            buf = buf.at[dest_loc].add(x_rep * wts[:, None])
            expert_in = buf[: E_loc * C].reshape(E_loc, C, Dm)
            sel = wts.astype(jnp.float32)
        else:
            w_in_g = _gather_fsdp(w_in, 1)       # (E, D, F_loc)
            w_gate_g = _gather_fsdp(w_gate, 1) if gated else None
            w_out_g = _gather_fsdp(w_out, 2)     # (E, F_loc, D)
            dest_loc = dest
            buf = jnp.zeros((E * C + 1, Dm), xt.dtype)
            buf = buf.at[dest_loc].add(x_rep * keep[:, None])
            expert_in = buf[: E * C].reshape(E, C, Dm)
            sel = keepf

        hmid = jnp.einsum("ecd,edf->ecf", expert_in, w_in_g)
        if gated:
            g = jnp.einsum("ecd,edf->ecf", expert_in, w_gate_g)
            hmid = activation(g, cfg.hidden_act) * hmid
        else:
            hmid = activation(hmid, cfg.hidden_act)
        out = jnp.einsum("ecf,efd->ecd", hmid, w_out_g)
        out_pad = jnp.concatenate(
            [out.reshape(-1, Dm), jnp.zeros((1, Dm), out.dtype)], axis=0
        )
        got = out_pad[dest_loc]  # (t_loc·K, D); zeros where not owned/dropped
        w8 = gate.reshape(-1).astype(jnp.float32) * sel
        y = (got.astype(jnp.float32) * w8[:, None]).reshape(t_loc, K, Dm).sum(axis=1)
        # combine psum rides the wire in bf16 (§Perf cell B): halves the one
        # MoE collective; the f32 partial sums are formed before the cast.
        y = jax.lax.psum(y.astype(jnp.bfloat16), "model")
        return y.reshape(b_loc, s, Dm).astype(xl.dtype), aux

    args = [x, params["router"], params["w_in"],
            params["w_gate"] if gated else params["w_in"], params["w_out"]]
    in_specs = (x_spec, r_spec, w_in_spec, w_in_spec, w_out_spec)
    y, aux = jax.shard_map(
        inner, mesh=mesh, in_specs=in_specs,
        out_specs=(x_spec, P_()), check_vma=False,
    )(*args)
    return y, aux
