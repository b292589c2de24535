"""Jitted wrapper: quantized-cache decode attention."""

from __future__ import annotations

import functools

import jax

from .kernel import decode_attention_int8
from .ref import decode_attention_int8_ref, dequantize_kv, quantize_kv


@functools.partial(jax.jit, static_argnames=("scale", "block_s", "interpret",
                                             "use_kernel"))
def decode_attention_int8_op(q, k, k_scale, v, v_scale, pos, *, scale,
                             block_s: int = 512, interpret: bool = False,
                             use_kernel: bool = True):
    if use_kernel:
        return decode_attention_int8(q, k, k_scale, v, v_scale, pos,
                                     scale=scale, block_s=block_s,
                                     interpret=interpret)
    return decode_attention_int8_ref(q, k, k_scale, v, v_scale, pos,
                                     scale=scale)
