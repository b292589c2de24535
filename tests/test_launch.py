"""Launch-stack tests: dry-run machinery on a small virtual mesh
(subprocess: device count must be set before jax init), HLO cost model
closed-form validation, sharding rules invariants."""

import os
import subprocess
import sys

import pytest
from hypothesis import given, settings, strategies as st

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    return subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, cwd=REPO, timeout=600)


class TestHloCost:
    def test_scan_flops_closed_form(self):
        """FLOPs of a scanned matmul must equal trips × 2·M·N·K exactly."""
        code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
def f(w, x):
    def body(c, _):
        return jnp.tanh(c @ w), None
    y, _ = jax.lax.scan(body, x, None, length=7)
    return y
mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
comp = jax.jit(f, in_shardings=(NamedSharding(mesh, P("data", "model")),
                                NamedSharding(mesh, P(None, "data")))).lower(
    jax.ShapeDtypeStruct((256, 256), jnp.float32),
    jax.ShapeDtypeStruct((128, 256), jnp.float32)).compile()
from repro.hlocost import analyze_text
t = analyze_text(comp.as_text())
assert t.flops == 7 * 2 * 128 * 128 * 128, t.flops   # per-device shapes
assert t.collective_counts.get("all-reduce", 0) == 7
print("OK")
"""
        r = _run(code)
        assert r.returncode == 0 and "OK" in r.stdout, r.stderr[-3000:]

    def test_wire_bytes_formulas(self):
        from repro.hlocost import _wire_bytes
        n = 8
        assert _wire_bytes("all-gather", 800, n) == 800 * 7 / 8
        assert _wire_bytes("all-reduce", 800, n) == 2 * 800 * 7 / 8
        assert _wire_bytes("reduce-scatter", 100, n) == 700
        assert _wire_bytes("collective-permute", 123, n) == 123


class TestDryRunSmoke:
    """Reduced-config lower+compile on an 8-device virtual mesh: exercises
    build_cell / shardings / roofline end-to-end inside pytest."""

    @pytest.mark.parametrize("arch,shape", [
        ("stablelm-3b", "train_4k"),
        ("olmoe-1b-7b", "decode_32k"),
        ("mamba2-780m", "long_500k"),
    ])
    def test_cell_compiles_small(self, arch, shape):
        code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, jax
from repro.configs import get_config, reduced
from repro.launch.specs import build_cell
from repro.models.config import SHAPES
cfg = reduced(get_config({arch!r}))
shape = dataclasses.replace(SHAPES[{shape!r}], seq_len=256, global_batch=8)
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
with mesh:
    cell = build_cell(cfg, shape, mesh, loss_chunk=64)
    compiled = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                       out_shardings=cell.out_shardings,
                       donate_argnums=cell.donate_argnums).lower(*cell.args).compile()
from repro import hlocost
t = hlocost.analyze_text(compiled.as_text())
assert t.flops > 0
print("OK", t.flops)
"""
        r = _run(code)
        assert r.returncode == 0 and "OK" in r.stdout, r.stderr[-3000:]


class TestShardingRules:
    def test_specs_cover_param_tree(self):
        """INVARIANT: param_specs structure matches the init params exactly
        for every arch (both layouts)."""
        code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from jax.sharding import PartitionSpec as P
from repro.configs import ARCHS, get_config
from repro.distrib.sharding import Rules
from repro.models import build_model
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
for arch in ARCHS:
    cfg = get_config(arch)
    model = build_model(cfg)
    shapes = jax.eval_shape(lambda: model.init(0))
    for wf in (True, False):
        specs = Rules(mesh, weight_fsdp=wf).param_specs(cfg)
        jax.tree.map(lambda sh, sp: None, shapes, specs)  # same structure
        flat_sh = jax.tree.leaves(shapes)
        flat_sp = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        assert len(flat_sh) == len(flat_sp), arch
        for sh, sp in zip(flat_sh, flat_sp):
            assert len(sp) <= len(sh.shape), (arch, sh.shape, sp)
print("OK")
"""
        r = _run(code)
        assert r.returncode == 0 and "OK" in r.stdout, r.stderr[-3000:]

    @settings(max_examples=25, deadline=None)
    @given(dim=st.integers(1, 64), msize=st.sampled_from([2, 4, 8, 16]))
    def test_model_if_divisibility(self, dim, msize):
        """INVARIANT: a sharded dim always divides the axis."""
        # pure logic check (no mesh needed): mirrors Rules.model_if
        axis = "model" if dim % msize == 0 else None
        if axis is not None:
            assert dim % msize == 0


def _load_replay_cli():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "launch_serve_cli", os.path.join(REPO, "launch", "serve.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestServeExitCode:
    """Both serve CLIs exit non-zero when a replayed request failed, unless
    a chaos profile made failures the expected outcome."""

    TRACE = ["--rps", "10", "--duration", "0.5", "--functions", "2",
             "--workers", "1", "--time-scale", "0"]

    @pytest.fixture
    def failing(self, monkeypatch):
        from repro.serving.worker import Worker

        def arm():
            def broken(self, request):
                raise RuntimeError("injected handler failure")
            monkeypatch.setattr(Worker, "invoke", broken)
        return arm

    @pytest.mark.parametrize("fail,expected", [(False, 0), (True, 1)])
    def test_strategy_cli_trace_mode(self, tmp_path, monkeypatch, failing,
                                     fail, expected):
        from repro.launch import serve

        monkeypatch.setattr(serve, "enable_compilation_cache", lambda: "")
        if fail:
            failing()
        rc = serve.main(["--trace", "poisson", "--root", str(tmp_path),
                         *self.TRACE])
        assert rc == expected

    @pytest.mark.parametrize("fail,chaos,expected", [
        (False, None, 0), (True, None, 1), (True, "lossy-disk", 0),
    ])
    def test_replay_cli(self, tmp_path, monkeypatch, failing, fail, chaos,
                        expected):
        cli = _load_replay_cli()
        monkeypatch.setattr(cli, "enable_compilation_cache", lambda: "")
        if fail:
            failing()
        argv = ["--root", str(tmp_path), *self.TRACE]
        if chaos is not None:
            argv += ["--chaos", chaos]
        assert cli.main(argv) == expected


class TestCompilationCache:
    """An explicit JAX_COMPILATION_CACHE_DIR is left to JAX; otherwise the
    cache is the fixed repository path and keeps every compile."""

    KEYS = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")

    @pytest.fixture
    def jax_config(self):
        import jax

        prev = {k: getattr(jax.config, k) for k in self.KEYS}
        yield jax.config
        for k, v in prev.items():
            jax.config.update(k, v)

    @pytest.mark.parametrize("env_dir", [None, "/elsewhere/cache"])
    def test_placement(self, monkeypatch, jax_config, env_dir):
        from repro.launch import cache

        if env_dir is None:
            monkeypatch.delenv(cache.ENV_VAR, raising=False)
        else:
            monkeypatch.setenv(cache.ENV_VAR, env_dir)
        before = {k: getattr(jax_config, k) for k in self.KEYS}
        got = cache.enable_compilation_cache()
        after = {k: getattr(jax_config, k) for k in self.KEYS}
        if env_dir is None:
            assert got == str(cache.REPO_CACHE_DIR)
            assert str(cache.REPO_CACHE_DIR.parent) == os.path.realpath(REPO)
            assert after == {"jax_compilation_cache_dir": got,
                             "jax_persistent_cache_min_compile_time_secs": 0.0}
        else:
            assert got == env_dir
            assert after == before
