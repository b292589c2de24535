"""The unified LM: dense / MoE / SSM / hybrid / enc-dec / VLM backbones.

One code path serves all ten assigned architectures: a macro-block plan
(``blocks.build_plan``) describes the repeating layer structure, parameters
are stacked over macro-block repeats, and the whole stack lowers as a single
``lax.scan`` (compile time O(period), not O(layers)).

Three entry modes:
  * ``forward``      — full-sequence logits (training; prefill reuses it)
  * ``prefill``      — forward + KV/SSM cache construction
  * ``decode_step``  — one token against a cache (serving decode)
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distrib.act import shard

from . import blocks as blocks_mod
from .attention import blockwise_attention, decode_attention
from .config import LayerKind, ModelConfig
from .layers import apply_norm, apply_rope, mlp, sinusoidal_positions, softcap
from .moe import moe_ffn
from .ssm import mamba_mixer

PyTree = Any


def _norm_param(cfg: ModelConfig, key, D: int) -> Dict[str, jax.Array]:
    if cfg.norm == "rmsnorm":
        return {"scale": jnp.zeros((D,), jnp.float32)}
    return {"scale": jnp.ones((D,), jnp.float32), "bias": jnp.zeros((D,), jnp.float32)}


def _init(key, shape, scale=0.02, dtype=jnp.float32):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# parameter construction
# ---------------------------------------------------------------------------

def init_layer(cfg: ModelConfig, kind: LayerKind, key, *, cross: bool = False) -> PyTree:
    D, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 16)
    p: Dict[str, Any] = {"ln1": _norm_param(cfg, ks[0], D)}
    dt = jnp.dtype(cfg.dtype)
    if kind.mixer == "attn":
        p["wq"] = _init(ks[1], (D, H, hd), dtype=dt)
        p["wk"] = _init(ks[2], (D, KV, hd), dtype=dt)
        p["wv"] = _init(ks[3], (D, KV, hd), dtype=dt)
        p["wo"] = _init(ks[4], (H, hd, D), dtype=dt)
    else:
        d_in, ds, nh = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
        p["w_z"] = _init(ks[1], (D, d_in), dtype=dt)
        p["w_xBC"] = _init(ks[2], (D, d_in + 2 * ds), dtype=dt)
        p["w_dt"] = _init(ks[3], (D, nh), dtype=dt)
        p["dt_bias"] = jnp.zeros((nh,), jnp.float32)
        p["conv_w"] = _init(ks[4], (cfg.ssm_conv, d_in + 2 * ds), scale=0.1)
        p["conv_b"] = jnp.zeros((d_in + 2 * ds,), jnp.float32)
        p["A_log"] = jnp.log(jnp.arange(1, nh + 1, dtype=jnp.float32))
        p["D"] = jnp.ones((nh,), jnp.float32)
        p["gate_norm"] = jnp.zeros((d_in,), jnp.float32)
        p["w_out"] = _init(ks[5], (d_in, D), dtype=dt)
    if cross:
        p["ln_cross"] = _norm_param(cfg, ks[6], D)
        p["cq"] = _init(ks[7], (D, H, hd), dtype=dt)
        p["ck"] = _init(ks[8], (D, KV, hd), dtype=dt)
        p["cv"] = _init(ks[9], (D, KV, hd), dtype=dt)
        p["co"] = _init(ks[10], (H, hd, D), dtype=dt)
    if kind.ffn != "none":
        p["ln2"] = _norm_param(cfg, ks[11], D)
        if kind.ffn == "moe":
            F = cfg.moe_d_ff
            p["ffn"] = {
                "router": _init(ks[12], (D, cfg.num_experts), dtype=jnp.float32),
                "w_in": _init(ks[13], (cfg.num_experts, D, F), dtype=dt),
                "w_out": _init(ks[14], (cfg.num_experts, F, D), dtype=dt),
            }
            if cfg.mlp_gated:
                p["ffn"]["w_gate"] = _init(ks[15], (cfg.num_experts, D, F), dtype=dt)
        else:
            F = cfg.d_ff
            p["ffn"] = {
                "w_in": _init(ks[12], (D, F), dtype=dt),
                "w_out": _init(ks[13], (F, D), dtype=dt),
            }
            if cfg.mlp_gated:
                p["ffn"]["w_gate"] = _init(ks[14], (D, F), dtype=dt)
    return p


def _stack_layers(cfg: ModelConfig, kinds, n_repeat: int, key, *, cross=False) -> PyTree:
    """Params for one macro-block position, stacked over n_repeat."""
    out = {}
    for i, kind in enumerate(kinds):
        keys = jax.random.split(jax.random.fold_in(key, i), n_repeat)
        per = [init_layer(cfg, kind, k, cross=cross) for k in keys]
        out[f"pos{i}"] = jax.tree.map(lambda *xs: jnp.stack(xs), *per)
    return out


def init_params(cfg: ModelConfig, seed: int = 0) -> PyTree:
    key = jax.random.PRNGKey(seed)
    kb, ke, kh, kenc, kdec = jax.random.split(key, 5)
    plan = blocks_mod.build_plan(cfg)
    dt = jnp.dtype(cfg.dtype)
    params: Dict[str, Any] = {
        "embed": {"table": _init(ke, (cfg.vocab_size, cfg.d_model), dtype=dt)},
        "final_norm": _norm_param(cfg, kh, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"w": _init(kh, (cfg.d_model, cfg.vocab_size), dtype=dt)}
    if cfg.is_encoder_decoder:
        enc_kind = LayerKind(mixer="attn", ffn="mlp")
        params["enc"] = {
            "blocks": _stack_layers(cfg, (enc_kind,), cfg.num_layers, kenc),
            "final_norm": _norm_param(cfg, kenc, cfg.d_model),
        }
        dec_kind = LayerKind(mixer="attn", ffn="mlp")
        params["blocks"] = _stack_layers(
            cfg, (dec_kind,), cfg.num_decoder_layers, kdec, cross=True
        )
    else:
        params["blocks"] = _stack_layers(cfg, plan.kinds, plan.n_repeat, kb)
    return params


# ---------------------------------------------------------------------------
# layer application
# ---------------------------------------------------------------------------

def _attn_qkv(p, h, cfg, positions):
    # Separate Q/K/V dots with weights explicitly gathered to compute (TP)
    # layout.  NOTE (§Perf cell A, iteration 2 — REFUTED): fusing qkv into
    # one concatenated dot to merge the three backward input-grad
    # all-reduces into one measured WORSE (Tx 20.2 s → 33.5 s): the
    # concat+slice forces GSPMD to re-shard the fused weight and its
    # gradient every microbatch, dwarfing the saved ARs.
    wq = shard(p["wq"], None, "heads", None)
    wk = shard(p["wk"], None, "kv_heads", None)
    wv = shard(p["wv"], None, "kv_heads", None)
    q = shard(jnp.einsum("bld,dhk->blhk", h, wq),
              "batch", "seq", "heads", "head_dim")
    k = shard(jnp.einsum("bld,dgk->blgk", h, wk),
              "batch", "seq", "kv_heads", "head_dim")
    v = shard(jnp.einsum("bld,dgk->blgk", h, wv),
              "batch", "seq", "kv_heads", "head_dim")
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _scale(cfg: ModelConfig) -> float:
    return (
        cfg.query_scale if cfg.query_scale is not None else 1.0 / float(np.sqrt(cfg.head_dim))
    )


def apply_layer(
    cfg: ModelConfig,
    kind: LayerKind,
    p: PyTree,
    h: jax.Array,
    *,
    positions: jax.Array,
    cache: Optional[PyTree] = None,
    decode: bool = False,
    pos: Optional[jax.Array] = None,
    prefix_len: int = 0,
    causal: bool = True,
    cross_states: Optional[jax.Array] = None,
    make_cache: bool = False,
    cache_len: int = 0,
) -> Tuple[jax.Array, Optional[PyTree], jax.Array]:
    """One layer. Returns (h, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    new_cache: Dict[str, Any] = {}
    # the residual stream h may be f32 (carry precision); compute in cfg dtype
    cdt = jnp.dtype(cfg.dtype) if h.dtype == jnp.float32 else h.dtype
    x = apply_norm(h, p["ln1"], cfg).astype(cdt)

    if kind.mixer == "attn":
        window = cfg.sliding_window if kind.is_local else 0
        if decode:
            assert cache is not None and pos is not None
            q = jnp.einsum("bld,dhk->blhk", x, p["wq"])
            k = jnp.einsum("bld,dgk->blgk", x, p["wk"])
            v = jnp.einsum("bld,dgk->blgk", x, p["wv"])
            if cfg.use_rope:
                posb = jnp.full((x.shape[0], 1), pos, jnp.int32)
                q = apply_rope(q, posb, cfg.rope_theta)
                k = apply_rope(k, posb, cfg.rope_theta)
            k_cache = shard(
                jax.lax.dynamic_update_slice_in_dim(
                    cache["k"], k.astype(cache["k"].dtype), pos, 1),
                "batch", None, "kv_heads", "cache_hd")
            v_cache = shard(
                jax.lax.dynamic_update_slice_in_dim(
                    cache["v"], v.astype(cache["v"].dtype), pos, 1),
                "batch", None, "kv_heads", "cache_hd")
            attn = decode_attention(
                q, k_cache, v_cache, pos, scale=_scale(cfg), window=window,
                logit_softcap=cfg.attn_logit_softcap,
            )
            new_cache = {"k": k_cache, "v": v_cache}
        else:
            q, k, v = _attn_qkv(p, x, cfg, positions)
            attn = shard(
                blockwise_attention(
                    q, k, v, scale=_scale(cfg), causal=causal, window=window,
                    prefix_len=prefix_len, logit_softcap=cfg.attn_logit_softcap,
                ),
                "batch", "seq", "heads", "head_dim",
            )
            if make_cache:
                pad = cache_len - k.shape[1]
                kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
                vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
                new_cache = {"k": kc, "v": vc}
        wo = shard(p["wo"], "heads", None, None)
        h = h + shard(jnp.einsum("blhk,hkd->bld", attn, wo),
                      "batch", "seq", "embed")
    else:  # mamba
        out, mcache = mamba_mixer(p, x, cfg, cache=cache, decode=decode)
        h = h + shard(out, "batch", "seq", "embed")
        if (decode or make_cache) and mcache is not None:
            new_cache = mcache

    # cross-attention (whisper decoder)
    if cross_states is not None:
        xc = apply_norm(h, p["ln_cross"], cfg).astype(cdt)
        q = jnp.einsum("bld,dhk->blhk", xc, p["cq"])
        if decode:
            assert cache is not None and "ck" in cache
            ck, cv = cache["ck"], cache["cv"]
            enc_len = ck.shape[1]
            attn = decode_attention(
                q, ck, cv, jnp.asarray(enc_len - 1, jnp.int32), scale=_scale(cfg),
            )
            new_cache.update({"ck": ck, "cv": cv})
        else:
            ck = jnp.einsum("bld,dgk->blgk", cross_states, p["ck"])
            cv = jnp.einsum("bld,dgk->blgk", cross_states, p["cv"])
            attn = blockwise_attention(q, ck, cv, scale=_scale(cfg), causal=False)
            if make_cache:
                new_cache.update({"ck": ck, "cv": cv})
        h = h + jnp.einsum("blhk,hkd->bld", attn, p["co"])

    if kind.ffn != "none":
        x2 = apply_norm(h, p["ln2"], cfg).astype(cdt)
        if kind.ffn == "moe":
            from repro.distrib.act import current_binding
            from .moe import moe_ffn_sharded

            # decode: a handful of tokens — use drop-free capacity so decode
            # agrees with teacher-forced forward (capacity dropping is a
            # training-throughput trade, not a serving one).
            cf = float(cfg.num_experts) / cfg.num_experts_per_tok if decode else None
            impl = moe_ffn_sharded if current_binding() is not None else moe_ffn
            y, aux = impl(p["ffn"], x2, cfg, capacity_factor=cf)
        else:
            y = mlp(p["ffn"], x2, cfg.hidden_act, cfg.mlp_gated)
        h = h + y
    return h, (new_cache or None), aux


# ---------------------------------------------------------------------------
# stack application (one lax.scan over macro-blocks)
# ---------------------------------------------------------------------------

def apply_stack(
    cfg: ModelConfig,
    kinds,
    blocks_params: PyTree,
    h: jax.Array,
    *,
    positions: jax.Array,
    cache: Optional[PyTree] = None,
    decode: bool = False,
    pos: Optional[jax.Array] = None,
    prefix_len: int = 0,
    causal: bool = True,
    cross_states: Optional[jax.Array] = None,
    make_cache: bool = False,
    cache_len: int = 0,
    remat: bool = False,
    remat_group: int = 1,
) -> Tuple[jax.Array, Optional[PyTree], jax.Array]:
    """Scan over stacked macro-blocks. Returns (h, caches, aux).

    ``remat_group > 1`` uses two-level remat (scan of checkpointed scans):
    the h-stack peak drops from O(n_repeat) to O(n_repeat/g + g) slices at
    the cost of one extra forward recompute — required for 64-layer 314B
    training to fit 16 GB HBM."""

    def body(carry, xs):
        hh, aux_acc = carry
        bp = xs[0]
        cslice = xs[1] if cache is not None else None
        new_cs: Dict[str, Any] = {}
        for i, kind in enumerate(kinds):
            c_i = cslice.get(f"pos{i}") if cslice is not None else None
            hh, nc, aux = apply_layer(
                cfg, kind, bp[f"pos{i}"], hh,
                positions=positions, cache=c_i, decode=decode, pos=pos,
                prefix_len=prefix_len, causal=causal, cross_states=cross_states,
                make_cache=make_cache, cache_len=cache_len,
            )
            if nc is not None:
                new_cs[f"pos{i}"] = nc
            aux_acc = aux_acc + aux
        ys = new_cs if (decode or make_cache) and new_cs else None
        return (hh, aux_acc), ys

    # f32 residual stream: the scan carry (= the remat h-stack under
    # training) is stored once in f32 instead of bf16 + an XLA-hoisted f32
    # copy of the whole stack (measured 3× the bf16 stack otherwise); a
    # config with ``residual_in_fp32`` carries it so in every mode.
    # Per-layer compute still runs in cfg.dtype (see apply_layer).
    if remat or cfg.residual_in_fp32:
        h = h.astype(jnp.float32)
    carry0 = (h, jnp.zeros((), jnp.float32))

    n_repeat = jax.tree.leaves(blocks_params)[0].shape[0]
    if (
        remat and remat_group > 1 and cache is None and not make_cache
        and n_repeat % remat_group == 0
    ):
        inner = jax.checkpoint(body, prevent_cse=False)
        gxs = jax.tree.map(
            lambda x: x.reshape((n_repeat // remat_group, remat_group) + x.shape[1:]),
            blocks_params,
        )

        def group_body(carry, gx):
            c, _ = jax.lax.scan(inner, carry, (gx,))
            return c, None

        group_body = jax.checkpoint(group_body, prevent_cse=False)
        (h, aux), caches = jax.lax.scan(group_body, carry0, gxs)
        return h, caches, aux

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    xs = (blocks_params, cache) if cache is not None else (blocks_params,)
    (h, aux), caches = jax.lax.scan(body, carry0, xs)
    return h, caches, aux


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def chunked_cross_entropy(
    h: jax.Array,          # (b, s, D) final hidden states
    embed_table: jax.Array,  # (V, D) (tied) — or head (D, V) via transpose flag
    labels: jax.Array,     # (b, s) int32, -1 = ignore
    *,
    final_softcap: float = 0.0,
    chunk: int = 1024,
    transpose_head: bool = False,
) -> jax.Array:
    """Cross-entropy without materializing (b, s, V) logits: scan over
    sequence chunks. At gemma's 256k vocab the full logits tensor is tens of
    GB per device; this keeps live memory at (b, chunk, V)."""
    b, s, D = h.shape
    chunk = min(chunk, s)
    if s % chunk != 0:  # vlm text lengths (seq − prefix) need a divisor
        import math

        chunk = math.gcd(s, chunk) or s
    nc = s // chunk
    hs = h.reshape(b, nc, chunk, D).transpose(1, 0, 2, 3)
    ls = labels.reshape(b, nc, chunk).transpose(1, 0, 2)

    W = embed_table if transpose_head else embed_table.T  # (D, V)

    def step(acc, inp):
        hc, lc = inp
        logits = jnp.einsum("bcd,dv->bcv", hc.astype(W.dtype), W,
                            preferred_element_type=jnp.float32)
        if final_softcap > 0.0:
            logits = softcap(logits, final_softcap)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, jnp.maximum(lc, 0)[..., None], axis=-1)[..., 0]
        valid = (lc >= 0).astype(jnp.float32)
        nll = (logz - gold) * valid
        return (acc[0] + nll.sum(), acc[1] + valid.sum()), None

    (tot, cnt), _ = jax.lax.scan(step, (jnp.zeros(()), jnp.zeros(())), (hs, ls))
    return tot / jnp.maximum(cnt, 1.0)
