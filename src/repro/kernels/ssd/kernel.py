"""Pallas TPU kernel for the Mamba-2 SSD chunked scan.

Grid = (batch, heads, chunks); the chunk dimension is sequential
("arbitrary") and the inter-chunk SSM state (hd × ds) lives in VMEM scratch —
the only sequential dependence in SSD.  Per grid step everything is dense
MXU work on (c×ds)·(ds×c) and (c×c)·(c×hd) tiles: this is the TPU-native
blocking of the selective scan (DESIGN.md §6).

VMEM per step at c=256, hd=64, ds=128 (f32): x 64 KB + B,C 2·128 KB +
decay/M 2·256 KB + state 32 KB ≈ 0.9 MB — small; double buffering and a
second head's blocks fit easily.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(
    x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref,  # inputs
    y_ref, state_out_ref,                        # outputs
    state_ref,                                   # VMEM scratch (hd, ds)
    *,
    chunk: int,
    nc: int,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, :, 0, :].astype(jnp.float32)    # (c, hd)
    dt = dt_ref[0, :, 0].astype(jnp.float32)     # (c,)
    A = a_ref[0].astype(jnp.float32)             # scalar
    B = b_ref[0].astype(jnp.float32)             # (c, ds)
    C = c_ref[0].astype(jnp.float32)             # (c, ds)
    D = d_ref[0].astype(jnp.float32)             # scalar

    da = dt * A                                   # (c,) ≤ 0
    cs = jnp.cumsum(da)                           # (c,)
    # intra-chunk quadratic term
    CB = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (c, c)
    i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    expnt = cs[:, None] - cs[None, :]
    decay = jnp.exp(jnp.where(i >= j, expnt, -jnp.inf))
    M = CB * decay * dt[None, :]
    y = jax.lax.dot_general(M, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (c, hd)
    # inter-chunk: incoming state contribution
    state = state_ref[...]                         # (hd, ds)
    Cst = jax.lax.dot_general(C, state, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (c, hd)
    y = y + Cst * jnp.exp(cs)[:, None]
    y = y + D * x
    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)
    # state passing
    total = cs[-1]
    w = dt * jnp.exp(total - cs)                   # (c,)
    state_chunk = jax.lax.dot_general(
        x, B * w[:, None], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (hd, ds)
    state_ref[...] = state * jnp.exp(total) + state_chunk

    @pl.when(ic == nc - 1)
    def _final():
        state_out_ref[0, 0] = state_ref[...].astype(state_out_ref.dtype)


def ssd_scan(
    x: jax.Array,   # (b, l, nh, hd)
    dt: jax.Array,  # (b, l, nh)
    A: jax.Array,   # (nh,)
    B: jax.Array,   # (b, l, ds)
    C: jax.Array,   # (b, l, ds)
    D: jax.Array,   # (nh,)
    *,
    chunk: int = 256,
    interpret: bool = False,
):
    b, l, nh, hd = x.shape
    ds = B.shape[-1]
    chunk = min(chunk, l)
    assert l % chunk == 0
    nc = l // chunk
    grid = (b, nh, nc)
    kern = functools.partial(_kernel, chunk=chunk, nc=nc)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 1, hd), lambda ib, ih, ic: (ib, ic, ih, 0)),
            pl.BlockSpec((1, chunk, 1), lambda ib, ih, ic: (ib, ic, ih)),
            pl.BlockSpec((1,), lambda ib, ih, ic: (ih,)),
            pl.BlockSpec((1, chunk, ds), lambda ib, ih, ic: (ib, ic, 0)),
            pl.BlockSpec((1, chunk, ds), lambda ib, ih, ic: (ib, ic, 0)),
            pl.BlockSpec((1,), lambda ib, ih, ic: (ih,)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, hd), lambda ib, ih, ic: (ib, ic, ih, 0)),
            pl.BlockSpec((1, 1, hd, ds), lambda ib, ih, ic: (ib, ih, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, l, nh, hd), x.dtype),
            jax.ShapeDtypeStruct((b, nh, hd, ds), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, ds), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, dt, A, B, C, D)
