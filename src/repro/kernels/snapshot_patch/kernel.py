"""Pallas TPU kernel: fused base⊕diff snapshot patch-apply.

The restore hot loop of the paper — assembling an instance's arrays from
base chunks (HBM-resident pool) and diff chunks (freshly streamed) — is a
selective copy.  On TPU the assembly runs as a single memory-bandwidth-bound
kernel: the per-chunk source selection is a *scalar-prefetch* index map, so
each output tile is DMA'd directly from whichever input owns it, with zero
branching in the data path.

Two modes:
  * replace — chunk-granular override (the paper's diff-over-base semantics)
  * add     — additive delta (merged-adapter / compressed-gradient restore),
              out = base + scale · diff

Layout: arrays are viewed as (n_chunks, chunk_elems).  ``sel`` maps output
chunk i → row of ``diff`` (or -1 → base row i).  Inside, each chunk is tiled
as (chunk_elems // 128, 128) with the chunk axis squeezed out of the block,
so a block's last two dims are the whole tile (the TPU's block-shape rule);
chunk_elems must therefore be a multiple of 128.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128  # last dim of a TPU vector tile


def _kernel_replace(sel_ref, base_ref, diff_ref, out_ref):
    i = pl.program_id(0)
    use_diff = sel_ref[i] >= 0
    out_ref[...] = jnp.where(use_diff, diff_ref[...], base_ref[...])


def _kernel_add(sel_ref, base_ref, diff_ref, out_ref, *, scale: float):
    i = pl.program_id(0)
    use_diff = (sel_ref[i] >= 0).astype(base_ref.dtype)
    out_ref[...] = base_ref[...] + scale * use_diff * diff_ref[...]


def patch_apply(
    base: jax.Array,   # (n, c)
    diff: jax.Array,   # (k, c)
    sel: jax.Array,    # (n,) int32: row into diff, or -1 → keep base
    *,
    mode: str = "replace",
    scale: float = 1.0,
    interpret: bool = False,
) -> jax.Array:
    n, c = base.shape
    assert diff.shape[1] == c and sel.shape == (n,)
    if c % LANES:
        raise ValueError(
            f"patch_apply: chunk of {c} elements is not a multiple of "
            f"{LANES}; the TPU kernel tiles chunks as (c // {LANES}, {LANES})"
        )

    if mode == "replace":
        kern = _kernel_replace
    elif mode == "add":
        kern = functools.partial(_kernel_add, scale=scale)
    else:
        raise ValueError(mode)

    tile = (None, c // LANES, LANES)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[
            pl.BlockSpec(tile, lambda i, sel: (i, 0, 0)),
            # fetch the selected diff row; clamp -1 → row 0 (discarded by the
            # in-kernel select) so the DMA address is always valid.
            pl.BlockSpec(tile, lambda i, sel: (jnp.maximum(sel[i], 0), 0, 0)),
        ],
        out_specs=pl.BlockSpec(tile, lambda i, sel: (i, 0, 0)),
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, c // LANES, LANES), base.dtype),
        interpret=interpret,
    )(sel, base.reshape(n, c // LANES, LANES),
      diff.reshape(diff.shape[0], c // LANES, LANES))
    return out.reshape(n, c)
