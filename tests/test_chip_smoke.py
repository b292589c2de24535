"""``chip_smoke.py`` at a tiny size on the CPU: its phases and checks run
end to end through the serving path, and the script itself refuses to run
without a TPU."""

from __future__ import annotations

import dataclasses
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_to_run_without_a_tpu(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, str(SCRIPT)], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_smoke_phases_pass_at_tiny_size(tmp_path, dtype):
    from repro.configs import get_config, reduced

    smoke = _load_smoke()
    cfg = dataclasses.replace(reduced(get_config(smoke.MODEL)), dtype=dtype)
    out = smoke.run(cfg, str(tmp_path), replay_s=1.0)
    assert sorted(out["strategies"]) == sorted(smoke.STRATEGIES)
    for rows in out["strategies"].values():
        assert len(rows) == smoke.N_FUNCTIONS
    patched = {r["function"]: r["device_patched"]
               for r in out["strategies"]["snapfaas"]}
    assert "embed/table" in patched["fn0-adapter"]
    assert "embed/table" in patched["fn1-head"]
    assert out["replay"]["n_failed"] == 0
    assert out["replay"]["n_completed"] > 0
