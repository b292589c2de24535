"""Shared primitive layers: norms, activations, RoPE, softcap, embeddings."""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def layernorm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


def apply_norm(x: jax.Array, p, cfg) -> jax.Array:
    """The config's norm (``cfg.norm``, RMSNorm at ``cfg.rms_norm_eps``)."""
    if cfg.norm == "rmsnorm":
        return rmsnorm(x, p["scale"], cfg.rms_norm_eps)
    return layernorm(x, p["scale"], p["bias"])


def activation(x: jax.Array, kind: str) -> jax.Array:
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x, approximate=True)
    raise ValueError(kind)


def softcap(x: jax.Array, cap: float) -> jax.Array:
    """Gemma-2 logit soft-capping: cap * tanh(x / cap)."""
    if cap <= 0.0:
        return x
    return cap * jnp.tanh(x / cap)


# --------------------------------------------------------------------- RoPE

def rope_angles(positions: jax.Array, head_dim: int, theta: float) -> Tuple[jax.Array, jax.Array]:
    """positions (...,) -> cos/sin of shape (..., head_dim // 2)."""
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (b, s, h, d), positions: (s,) or (b, s)."""
    d = x.shape[-1]
    cos, sin = rope_angles(positions, d, theta)  # (s, d/2) or (b, s, d/2)
    if cos.ndim == 2:  # (s, d/2) -> broadcast over batch & heads
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:  # (b, s, d/2)
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq: int, d_model: int) -> np.ndarray:
    """Whisper-style fixed sinusoidal embeddings, (seq, d_model)."""
    pos = np.arange(seq)[:, None]
    dim = np.arange(d_model // 2)[None, :]
    inv = 1.0 / (10000 ** (dim / max(1, d_model // 2 - 1)))
    ang = pos * inv
    return np.concatenate([np.sin(ang), np.cos(ang)], axis=-1).astype(np.float32)


# ----------------------------------------------------------------------- MLP

def mlp(params, x: jax.Array, act: str, gated: bool) -> jax.Array:
    from repro.distrib.act import shard

    # gather FSDP shards to compute (TP-only) layout before use.
    # NOTE (§Perf cell A, iteration 2 — REFUTED): fusing gate+in into one
    # concatenated dot (to merge their backward ARs) measured WORSE
    # (Tx 20.2 s → 35.3 s): GSPMD re-shards the concatenated weight and its
    # gradient around the FSDP storage layout every microbatch.
    w_in = shard(params["w_in"], None, "ffn")
    w_out = shard(params["w_out"], "ffn", None)
    h = jnp.einsum("...d,df->...f", x, w_in)
    if x.ndim == 3:
        h = shard(h, "batch", "seq", "ffn")
    if gated:
        g = jnp.einsum("...d,df->...f", x, shard(params["w_gate"], None, "ffn"))
        h = activation(g, act) * h
    else:
        h = activation(h, act)
    out = jnp.einsum("...f,fd->...d", h, w_out)
    if x.ndim == 3:
        out = shard(out, "batch", "seq", "embed")
    return out
