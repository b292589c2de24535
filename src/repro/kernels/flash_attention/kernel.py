"""Pallas TPU flash-attention kernel (online softmax, VMEM-tiled).

Target: TPU v5e MXU. Grid = (batch, q_heads, q_blocks, kv_blocks); the last
dimension is sequential ("arbitrary") so the (acc, m, l) VMEM scratch carries
the online-softmax state across KV blocks.  Fully-masked KV blocks (beyond
the causal frontier, or older than the sliding window) are skipped with
``pl.when`` — on TPU this avoids both the MXU work and the HBM→VMEM copy
cost of dead blocks, which is where the gemma-2 local layers win back their
FLOPs (see EXPERIMENTS.md §Perf).

Supports: GQA/MQA (kv head = q head // rep), causal & bidirectional,
sliding window, gemma-2 logit soft-capping.

Block sizes default to (bq, bk) = (512, 512): VMEM footprint per step is
q (bq·hd) + k,v (bk·hd) + scores (bq·bk) + acc (bq·hd) ≈ 1.8 MB at hd=128 in
f32 — comfortably under the ~16 MB v5e VMEM with double buffering.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(
    q_ref, k_ref, v_ref, o_ref,  # blocks
    acc_ref, m_ref, l_ref,       # VMEM scratch
    *,
    scale: float,
    causal: bool,
    window: int,
    softcap: float,
    bq: int,
    bk: int,
    nk: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = iq * bq
    k_start = ik * bk

    run = jnp.bool_(True)
    if causal:
        # block live iff some k_pos <= some q_pos: k_start <= q_end
        run = jnp.logical_and(run, k_start <= q_start + bq - 1)
    if window > 0:
        # block live iff some k_pos >= q_pos - window + 1 for some q
        run = jnp.logical_and(run, k_start + bk - 1 >= q_start - window + 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)  # (bk, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        allowed = jnp.ones((bq, bk), dtype=jnp.bool_)
        if causal:
            allowed = jnp.logical_and(allowed, k_pos <= q_pos)
        if window > 0:
            allowed = jnp.logical_and(allowed, q_pos - k_pos < window)
        s = jnp.where(allowed, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0, :, :] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,  # (b, nh, S, hd)
    k: jax.Array,  # (b, nkv, S, hd)
    v: jax.Array,  # (b, nkv, S, hd)
    *,
    scale: float,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    b, nh, S, hd = q.shape
    _, nkv, Sk, _ = k.shape
    rep = nh // nkv
    bq = min(block_q, S)
    bk = min(block_k, Sk)
    assert S % bq == 0 and Sk % bk == 0, (S, bq, Sk, bk)
    nq, nk = S // bq, Sk // bk

    grid = (b, nh, nq, nk)
    kern = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, softcap=softcap,
        bq=bq, bk=bk, nk=nk,
    )
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda ib, ih, iq, ik: (ib, ih // rep, ik, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda ib, ih, iq, ik: (ib, ih // rep, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, nh, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
