"""Pallas TPU decode-attention kernel with int8-quantized KV cache.

Every decode cell in the roofline table is memory-bound on the KV-cache
read (EXPERIMENTS.md §Roofline). Quantizing the cache to int8 halves that
traffic — but only if the dequantization happens *after* the HBM→VMEM copy,
in-register, which XLA will not do for the jnp path (it materializes the
converted bf16 tensor). This kernel loads int8 tiles + per-(position, head)
f32 scales and dequantizes in VMEM: the HBM side moves half the bytes.

Grid = (batch, kv_heads, S/block); the S dimension is sequential with the
online-softmax state for the GQA head group in VMEM scratch. The current
decode position rides in scalar-prefetch SMEM; blocks beyond it skip both
the MXU work and (on real TPUs) the HBM read.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(
    pos_ref,                                  # SMEM (1,) int32
    q_ref, k_ref, ks_ref, v_ref, vs_ref,      # blocks
    o_ref,                                    # out block
    acc_ref, m_ref, l_ref,                    # VMEM scratch
    *,
    scale: float,
    bs: int,
    ns: int,
):
    ik = pl.program_id(2)
    pos = pos_ref[0]

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(ik * bs <= pos)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                 # (rep, hd)
        k = k_ref[0, :, 0, :].astype(jnp.float32)           # (bs, hd) int8→f32
        k = k * ks_ref[0, :, 0].astype(jnp.float32)[:, None]  # dequant in VMEM
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                             # (rep, bs)
        k_pos = ik * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        s = jnp.where(k_pos <= pos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        v = v * vs_ref[0, :, 0].astype(jnp.float32)[:, None]
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ik == ns - 1)
    def _final():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def decode_attention_int8(
    q: jax.Array,        # (b, nh, hd)
    k: jax.Array,        # (b, S, nkv, hd) int8
    k_scale: jax.Array,  # (b, S, nkv) f32
    v: jax.Array,        # (b, S, nkv, hd) int8
    v_scale: jax.Array,  # (b, S, nkv) f32
    pos: jax.Array,      # scalar int32 — cache fill position (inclusive)
    *,
    scale: float,
    block_s: int = 512,
    interpret: bool = False,
) -> jax.Array:
    b, nh, hd = q.shape
    _, S, nkv, _ = k.shape
    rep = nh // nkv
    bs = min(block_s, S)
    assert S % bs == 0
    ns = S // bs

    kern = functools.partial(_kernel, scale=scale, bs=bs, ns=ns)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, nkv, ns),
        in_specs=[
            pl.BlockSpec((1, 1, rep, hd), lambda ib, ig, ik, pos: (ib, ig, 0, 0)),
            pl.BlockSpec((1, bs, 1, hd), lambda ib, ig, ik, pos: (ib, ik, ig, 0)),
            pl.BlockSpec((1, bs, 1), lambda ib, ig, ik, pos: (ib, ik, ig)),
            pl.BlockSpec((1, bs, 1, hd), lambda ib, ig, ik, pos: (ib, ik, ig, 0)),
            pl.BlockSpec((1, bs, 1), lambda ib, ig, ik, pos: (ib, ik, ig)),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, hd), lambda ib, ig, ik, pos: (ib, ig, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rep, hd), jnp.float32),
            pltpu.VMEM((rep,), jnp.float32),
            pltpu.VMEM((rep,), jnp.float32),
        ],
    )
    qr = q.reshape(b, nkv, rep, hd)
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nkv, rep, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(jnp.asarray(pos, jnp.int32).reshape(1), qr, k, k_scale, v, v_scale)
    return out.reshape(b, nh, hd)
