"""Jitted public wrapper for the flash-attention kernel.

Layout adapter: the model stack uses (b, s, heads, hd); the kernel tiles
(b, heads, s, hd).  ``flash_attention_op`` transposes at the boundary and
dispatches kernel vs. oracle (CPU tests pass interpret=True; by default
the kernel is compiled for the TPU)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import flash_attention
from .ref import attention_ref


@functools.partial(
    jax.jit,
    static_argnames=(
        "scale", "causal", "window", "softcap", "block_q", "block_k",
        "interpret", "use_kernel",
    ),
)
def flash_attention_op(
    q: jax.Array,  # (b, s, nh, hd) — model layout
    k: jax.Array,  # (b, s, nkv, hd)
    v: jax.Array,
    *,
    scale: float,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
    use_kernel: bool = True,
) -> jax.Array:
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if use_kernel:
        ot = flash_attention(
            qt, kt, vt, scale=scale, causal=causal, window=window,
            softcap=softcap, block_q=block_q, block_k=block_k,
            interpret=interpret,
        )
    else:
        ot = attention_ref(qt, kt, vt, scale=scale, causal=causal,
                           window=window, softcap=softcap)
    return ot.transpose(0, 2, 1, 3)
