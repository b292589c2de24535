"""Gradient compression for the slow (cross-pod / DCN) data-parallel axis.

At 2+ pods the "pod" axis rides DCN (~25 GB/s/host) rather than ICI; an
int8-with-error-feedback all-reduce cuts cross-pod gradient bytes 4×
(bf16→int8 payload + one f32 scale per tensor slice).

Primitives:
* ``quantize_int8`` / ``dequantize_int8`` — symmetric per-slice scaling
* ``ef_compressed_mean`` — shard_map'd cross-axis mean of *partial* grads:
  each shard quantizes (grad + carried error), all-gathers int8 over the
  axis, dequantizes and averages locally; the quantization residual is
  carried to the next step (error feedback keeps the method unbiased in
  the long run — standard 1-bit-Adam / PowerSGD-style EF).
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


PyTree = Any


def quantize_int8(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def ef_compressed_mean(
    partial: jax.Array,     # per-shard partial gradient (same shape everywhere)
    error: jax.Array,       # carried error-feedback buffer, same shape
    mesh: Mesh,
    axis: str,              # mesh axis to reduce over (e.g. "pod")
) -> Tuple[jax.Array, jax.Array]:
    """Mean of `partial` over `axis` using int8 payloads + error feedback.

    Inputs/outputs are sharded P(axis, ...) on a leading stacked dim: callers
    hold one partial per shard (shape (n, ...) with n = axis size).
    Returns (mean (n, ...) — identical content on every shard, still laid out
    P(axis, ...) — and the updated error buffer)."""
    n = mesh.shape[axis]

    def inner(p, e):
        p = p[0]  # local slice (leading dim 1)
        e = e[0]
        target = p + e
        q, s = quantize_int8(target)
        sent = dequantize_int8(q, s)
        e_new = target - sent
        qs = jax.lax.all_gather(q, axis)        # (n, ...) int8 on the wire
        ss = jax.lax.all_gather(s, axis)        # (n,) f32 scales
        mean = jnp.tensordot(ss, qs.astype(jnp.float32), axes=([0], [0])) / n
        return mean[None], e_new[None]

    other = tuple(a for a in mesh.axis_names if a != axis)
    in_spec = P(axis, *([None] * (partial.ndim - 1)))
    return jax.shard_map(
        inner, mesh=mesh, in_specs=(in_spec, in_spec),
        out_specs=(in_spec, in_spec), check_vma=False,
    )(partial, error)
