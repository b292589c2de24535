"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set XLA_FLAGS before any
device initialization.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod meshes: 256 chips per pod (16×16), 2 pods = 512 chips.

    Axes: "data" carries FSDP+DP, "model" carries TP/EP; the multi-pod run
    adds a leading "pod" axis (DP across pods — the slow DCN dimension)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(*, data: int = 2, model: int = 2):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    data = min(data, max(1, n // model))
    if data * model > n:
        model = 1
        data = n
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
