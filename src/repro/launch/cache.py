"""Persistent compilation cache placement for the entry points.

JAX's persistent cache keys on the directory it lives in, so a path that
moves between runs never hits.  ``enable_compilation_cache`` leaves an
explicit ``JAX_COMPILATION_CACHE_DIR`` entirely to JAX and otherwise points
the cache at one fixed directory, ``.jax_cache/`` at the repository root.
Entry points that compile call it once, before their first jit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: src/repro/launch/cache.py → repository root
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Turn on the persistent compilation cache; returns the directory in
    effect.  With ``JAX_COMPILATION_CACHE_DIR`` set, JAX has already read
    it and nothing is set here.  On the fixed path every compile is kept:
    JAX's default skips those under one second, and a whole forward of
    the served model compiles in less on a TPU v5e."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return str(REPO_CACHE_DIR)
