"""Jitted public wrapper for the SSD kernel."""

from __future__ import annotations

import functools

import jax

from .kernel import ssd_scan
from .ref import ssd_ref


@functools.partial(jax.jit, static_argnames=("chunk", "interpret", "use_kernel"))
def ssd_op(x, dt, A, B, C, D, *, chunk: int = 256, interpret: bool = False,
           use_kernel: bool = True):
    """Returns (y (b,l,nh,hd), final_state (b,nh,hd,ds))."""
    if use_kernel:
        return tuple(ssd_scan(x, dt, A, B, C, D, chunk=chunk, interpret=interpret))
    return ssd_ref(x, dt, A, B, C, D, chunk=chunk)
