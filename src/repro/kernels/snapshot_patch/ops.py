"""Jitted public wrapper for the snapshot patch-apply kernel."""

from __future__ import annotations

import functools

import jax

from .kernel import patch_apply
from .ref import patch_apply_ref


@functools.partial(jax.jit, static_argnames=("mode", "scale", "interpret", "use_kernel"))
def patch_apply_op(base, diff, sel, *, mode: str = "replace", scale: float = 1.0,
                   interpret: bool = False, use_kernel: bool = True):
    if use_kernel:
        return patch_apply(base, diff, sel, mode=mode, scale=scale,
                           interpret=interpret)
    return patch_apply_ref(base, diff, sel, mode=mode, scale=scale)
