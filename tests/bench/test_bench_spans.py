"""The readers of the program's spans: each on synthetic records and traces,
nothing where the program keeps no traces, and all of them in a traced run
of the tiny cell, where they agree with the program's own timings."""

import time

import numpy as np
import pytest

from bench import harness, loadgen
from bench.spans import cold_traces

MS = 1_000_000  # ns
NEW = ("alloc_ms", "read_ms", "verify_ms", "ram_hit", "land_ms", "patch_ms", "fwd_ms")


def _trace(fn, t0_ns, *, tiers=None):
    """A cold request's trace starting at ``t0_ns``: boot 0..60 ms (alloc 5,
    eager 5..50 with read 10 and verify 30, init 2), exec 60..100 ms (params
    60..80 with shapes 2 and two patches of 3 and 5, forward 80..99)."""
    from repro.core.metrics import ColdStartMetrics, RequestTrace, Span

    t = RequestTrace("0:0")

    def add(name, a, b, parent=None, **meta):
        s = Span(name, dict(meta))
        s.start_ns, s.end_ns, s.parent = t0_ns + a * MS, t0_ns + b * MS, parent
        t.spans.append(s)
        return s

    req = add("request", 0, 100, fn=fn)
    boot = add("boot", 0, 60, req)
    add("restore.alloc", 1, 6, boot)
    eager = add("restore.eager", 6, 51, boot)
    add("restore.read", 6, 16, eager)
    add("restore.verify", 16, 46, eager)
    add("restore.init", 51, 53, boot)
    ex = add("exec", 60, 100, req)
    params = add("exec.params", 60, 80, ex)
    add("exec.shapes", 60, 62, params)
    add("exec.patch", 65, 68, params)
    add("exec.patch", 70, 75, params)
    add("exec.forward", 80, 99, ex)
    t.metrics = ColdStartMetrics(tier_bytes=tiers if tiers is not None
                                 else {"ram": 300, "local": 100})
    return t


def _view(records):
    return harness.View(records=records, t0=0.0, t_end=1.0, seconds=1.0, setup_s=0.0,
                        traffic={}, config={}, peaks=None, trace=None, patched_bytes={},
                        flops_per_request=0.0)


def _record(k, fn, submit_s, done_s, cold=True):
    rec = loadgen.Record(index=k, function=fn, tokens=np.zeros((1, 4), np.int32))
    rec.submit, rec.done, rec.cold = submit_s, done_s, cold
    return rec


@pytest.fixture
def synthetic(monkeypatch):
    """Two cold requests and a warm one in the window, the traces of a
    warm-up request and of both cold requests in the program's ring."""
    import repro.core.metrics as metrics

    traces = [_trace("a", 0),                                  # warm-up, before the window
              _trace("a", 1000 * MS), _trace("b", 2000 * MS, tiers={"local": 400})]
    records = [_record(0, "a", 0.999, 1.101), _record(1, "b", 1.999, 2.101),
               _record(2, "a", 3.0, 3.05, cold=False)]
    monkeypatch.setattr(metrics, "recent_traces", lambda: list(traces))
    return _view(records), traces


def _read(name, view):
    path = harness.BENCH_DIR / "metrics" / f"{name}.py"
    return harness.load_module(path, f"bench_metric_{name}").read(view)


@pytest.mark.parametrize("name,want", [
    ("alloc_ms", 5.0), ("read_ms", 10.0), ("verify_ms", 30.0),
    # ram: 300 of 400 bytes, then 0 of 400
    ("ram_hit", 37.5),
    # params 20 ms less shapes 2 and patches 3 + 5
    ("land_ms", 10.0), ("patch_ms", 8.0), ("fwd_ms", 19.0),
])
def test_each_reader_on_synthetic_records(synthetic, name, want):
    view, _ = synthetic
    assert _read(name, view) == pytest.approx(want)


def test_requests_are_matched_to_their_own_traces(synthetic):
    view, traces = synthetic
    assert cold_traces(view) == traces[1:]
    view.records[1].function = "c"            # no trace of that function
    assert cold_traces(view) == traces[1:2]


@pytest.mark.parametrize("name", NEW)
def test_readers_find_nothing_without_program_traces(monkeypatch, synthetic, name):
    import repro.core.metrics as metrics

    view, _ = synthetic
    monkeypatch.delattr(metrics, "recent_traces")
    assert _read(name, view) is None
    monkeypatch.setattr(metrics, "recent_traces", lambda: [], raising=False)
    assert _read(name, view) is None


def test_a_traced_tiny_run_reports_every_span_metric(tiny_root, monkeypatch):
    cell = harness.load_cell(tiny_root, "stablelm3b.cold-rr")
    assert set(NEW) <= {m["name"] for m in cell.metrics("per_layer")}
    captured = {}
    view_cls = harness.View

    def keep(*a, **kw):
        captured["view"] = view_cls(*a, **kw)
        return captured["view"]

    monkeypatch.setattr(harness, "View", keep)
    out = harness.run_cell(tiny_root, "stablelm3b.cold-rr", 2**31 + 7, 1.0, True,
                           process_start=time.perf_counter(), require_tpu=False,
                           cache=False, log=lambda row: None)
    assert out["correct"], out["checks"]
    got = out["metrics"]
    assert set(NEW) <= set(got)
    view = captured["view"]
    traces = cold_traces(view)
    cold = [r for r in view.ok if r.cold]
    assert len(traces) == len(cold) > 0
    for r, t in zip(cold, traces):
        # the program's timings are the spans' lengths
        assert r.boot_s == t.total("repro.boot") and r.exec_s == t.total("repro.exec")
        # children cover their parent but for host noise (a tiny restore
        # takes under a millisecond here, so the share alone would swing)
        for parent in ("repro.boot", "repro.exec"):
            assert t.self_time(parent) <= max(0.05 * t.total(parent), 0.02)
        root = t.named("repro.request")[0]
        assert root.seconds >= 0.95 * (r.done - r.submit) - 0.02
    assert got["restore_ms"]["value"] == pytest.approx(
        np.mean([t.total("repro.boot") for t in traces]) * 1e3)
    assert got["exec_ms.cold"]["value"] == pytest.approx(
        np.mean([t.total("repro.exec") for t in traces]) * 1e3)
    assert 0.0 <= got["ram_hit"]["value"] <= 100.0
