"""Compile-only checks of the serving path's Pallas kernel for a TPU v5e.

The ``snapshot_patch`` kernel fuses base ⊕ diff in device memory on every
snapfaas cold start.  Interpret mode accepts block shapes the chip's
compiler refuses, so these tests compile the jitted wrapper the worker
calls (``patch_apply_op``) ahead of time for one described v5e chip, at
the geometries the serving path produces, and check that the kernel is in
the program (``tpu_custom_call``).  Nothing runs: no chip is needed.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.snapshot_patch import patch_apply_op


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # broad-ok: any failure means no described chip here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


# (rows, elements per chunk, dtype, mode, diff rows)
GEOMETRIES = {
    # stablelm-3b embed table (50304 x 2560) in bf16 at 64 KiB chunks
    "stablelm-embed-bf16-64k": (3930, 32768, jnp.bfloat16, "replace", 16),
    # faas-bench embed table (16384 x 384) in f32 at 256 KiB chunks
    "faas-bench-embed-f32-256k": (96, 65536, jnp.float32, "replace", 4),
    # f32 at 64 KiB chunks, additive patch
    "f32-64k-add": (64, 16384, jnp.float32, "add", 8),
}


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_patch_apply_compiles_for_v5e(one_chip, no_persistent_cache, geometry):
    n, c, dtype, mode, k = GEOMETRIES[geometry]
    base = jax.ShapeDtypeStruct((n, c), dtype, sharding=one_chip)
    diff = jax.ShapeDtypeStruct((k, c), dtype, sharding=one_chip)
    sel = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    compiled = patch_apply_op.lower(
        base, diff, sel, mode=mode, scale=0.5 if mode == "add" else 1.0,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
