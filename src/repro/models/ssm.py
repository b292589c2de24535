"""Mamba-2 SSD (state-space duality) mixer, chunked for TPU.

The chunked SSD decomposition (intra-chunk quadratic term + inter-chunk state
recurrence) is exactly the blocking the MXU wants: each chunk is a batch of
dense (c×c)·(c×hd) matmuls, and the only sequential dependence is a tiny
(nh, hd, ds) state carried across chunks — this is the TPU-native adaptation
of Mamba's GPU selective-scan (see DESIGN.md §6).

Jamba's Mamba-1 mixer is also realized through this SSD formulation (same
state-space family; scalar-per-head decay) — noted in DESIGN.md.

Shapes: x (b, l, nh, hd) · dt (b, l, nh) · A (nh,) · B,C (b, l, ds) · D (nh,)
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .layers import rmsnorm


def ssd_chunked(
    x: jax.Array,
    dt: jax.Array,
    A: jax.Array,
    B: jax.Array,
    C: jax.Array,
    D: jax.Array,
    *,
    chunk: int = 256,
    initial_state: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y (b,l,nh,hd), final_state (b,nh,hd,ds))."""
    b, l, nh, hd = x.shape
    ds = B.shape[-1]
    chunk = min(chunk, l)
    assert l % chunk == 0, (l, chunk)
    nc = l // chunk

    f32 = jnp.float32
    xc = x.reshape(b, nc, chunk, nh, hd)
    dtc = dt.reshape(b, nc, chunk, nh).astype(f32)
    Bc = B.reshape(b, nc, chunk, ds).astype(f32)
    Cc = C.reshape(b, nc, chunk, ds).astype(f32)
    A = A.astype(f32)

    state0 = (
        initial_state.astype(f32)
        if initial_state is not None
        else jnp.zeros((b, nh, hd, ds), f32)
    )

    def chunk_step(state, inp):
        x_c, dt_c, B_c, C_c = inp  # (b,c,nh,hd) (b,c,nh) (b,c,ds) (b,c,ds)
        da = dt_c * A  # (b,c,nh), ≤ 0
        cs = jnp.cumsum(da, axis=1)  # inclusive
        # --- intra-chunk (the "dual" quadratic form) ---
        CB = jnp.einsum("bis,bjs->bij", C_c, B_c)  # (b,c,c)
        i = jnp.arange(chunk)
        tri = i[:, None] >= i[None, :]
        # mask the exponent BEFORE exp: upper-triangle exponents are positive
        # and overflow to inf (inf · 0 = NaN after masking).
        expnt = cs[:, :, None, :] - cs[:, None, :, :]  # (b,c,c,nh)
        decay = jnp.exp(jnp.where(tri[None, :, :, None], expnt, -jnp.inf))
        M = CB[..., None] * decay * dt_c[:, None, :, :]
        y = jnp.einsum("bijn,bjnp->binp", M, x_c.astype(f32))
        # --- inter-chunk: contribution of the incoming state ---
        y = y + jnp.einsum("bis,bnps->binp", C_c, state) * jnp.exp(cs)[..., None]
        # --- state passing ---
        total = cs[:, -1, :]  # (b,nh)
        w = dt_c * jnp.exp(total[:, None, :] - cs)  # (b,c,nh)
        state_chunk = jnp.einsum("bjnp,bjs,bjn->bnps", x_c.astype(f32), B_c, w)
        state_new = state * jnp.exp(total)[:, :, None, None] + state_chunk
        y = y + D.astype(f32)[None, None, :, None] * x_c.astype(f32)
        return state_new, y.astype(x.dtype)

    final_state, ys = jax.lax.scan(
        chunk_step,
        state0,
        (
            xc.transpose(1, 0, 2, 3, 4),
            dtc.transpose(1, 0, 2, 3),
            Bc.transpose(1, 0, 2, 3),
            Cc.transpose(1, 0, 2, 3),
        ),
    )
    y = ys.transpose(1, 0, 2, 3, 4).reshape(b, l, nh, hd)
    return y, final_state


def ssd_decode_step(
    state: jax.Array,  # (b, nh, hd, ds)
    x: jax.Array,      # (b, nh, hd)
    dt: jax.Array,     # (b, nh)
    A: jax.Array,      # (nh,)
    B: jax.Array,      # (b, ds)
    C: jax.Array,      # (b, ds)
    D: jax.Array,      # (nh,)
) -> Tuple[jax.Array, jax.Array]:
    f32 = jnp.float32
    state = state.astype(f32)
    da = jnp.exp(dt.astype(f32) * A.astype(f32))  # (b, nh)
    upd = jnp.einsum("bnp,bs,bn->bnps", x.astype(f32), B.astype(f32), dt.astype(f32))
    state_new = state * da[:, :, None, None] + upd
    y = jnp.einsum("bnps,bs->bnp", state_new, C.astype(f32))
    y = y + D.astype(f32)[None, :, None] * x.astype(f32)
    return y.astype(x.dtype), state_new


# ------------------------------------------------------------- causal conv

def causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv. x (b, l, ch), w (width, ch), b (ch,)."""
    width = w.shape[0]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    l = x.shape[1]
    y = jnp.zeros_like(x, dtype=jnp.float32)
    for k in range(width):
        y = y + padded[:, k : k + l, :].astype(jnp.float32) * w[k][None, None, :]
    return (y + b[None, None, :]).astype(x.dtype)


def conv_step(
    conv_state: jax.Array,  # (b, width-1, ch) — trailing inputs
    x_t: jax.Array,         # (b, ch)
    w: jax.Array,
    b: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    window = jnp.concatenate([conv_state, x_t[:, None, :]], axis=1)  # (b,width,ch)
    y = jnp.einsum("bwc,wc->bc", window.astype(jnp.float32), w.astype(jnp.float32))
    y = (y + b[None, :]).astype(x_t.dtype)
    return y, window[:, 1:, :]


# ------------------------------------------------------------- full mixer

def mamba_mixer(
    params,
    h: jax.Array,  # (b, l, D)
    cfg,
    *,
    cache: Optional[dict] = None,
    decode: bool = False,
):
    """Mamba-2 block: in_proj → conv → SSD → gated norm → out_proj.

    Returns (out (b,l,D), new_cache | None). cache = {"conv": (b,w-1,ch),
    "ssm": (b,nh,hd,ds)}.
    """
    from repro.distrib.act import shard

    b, l, Dm = h.shape
    d_in, ds, nh, hd = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    w_z = shard(params["w_z"], None, "inner")
    w_xBC = shard(params["w_xBC"], None, None)
    z = shard(jnp.einsum("bld,de->ble", h, w_z), "batch", "seq", "inner")
    xBC = jnp.einsum("bld,de->ble", h, w_xBC)  # (b,l,d_in+2ds)
    xBC = shard(xBC, "batch", "seq", None)
    dt_raw = jnp.einsum("bld,dn->bln", h, params["w_dt"])  # (b,l,nh)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(params["A_log"].astype(jnp.float32))

    if decode:
        assert cache is not None and l == 1
        xBC_t, conv_state = conv_step(cache["conv"], xBC[:, 0], params["conv_w"], params["conv_b"])
        xBC_t = jax.nn.silu(xBC_t)
        x_t = xBC_t[:, :d_in].reshape(b, nh, hd)
        B_t = xBC_t[:, d_in : d_in + ds]
        C_t = xBC_t[:, d_in + ds :]
        y, ssm_state = ssd_decode_step(cache["ssm"], x_t, dt[:, 0], A, B_t, C_t, params["D"])
        y = y.reshape(b, 1, d_in)
        new_cache = {"conv": conv_state, "ssm": ssm_state}
    else:
        xBC_raw = xBC  # conv cache must hold the *pre-conv* inputs
        xBC = jax.nn.silu(causal_conv(xBC_raw, params["conv_w"], params["conv_b"]))
        x = xBC[..., :d_in].reshape(b, l, nh, hd)
        B = xBC[..., d_in : d_in + ds]
        C = xBC[..., d_in + ds :]
        y, ssm_state = ssd_chunked(x, dt, A, B, C, params["D"], chunk=cfg.ssm_chunk)
        y = y.reshape(b, l, d_in)
        conv_state = (
            xBC_raw[:, -(cfg.ssm_conv - 1) :, :] if l >= cfg.ssm_conv - 1 else None
        )
        new_cache = (
            {"conv": conv_state, "ssm": ssm_state} if conv_state is not None else None
        )

    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    y = rmsnorm(y, params["gate_norm"], cfg.rms_norm_eps)
    out = jnp.einsum("ble,ed->bld", y, shard(params["w_out"], "inner", None))
    return out, new_cache
