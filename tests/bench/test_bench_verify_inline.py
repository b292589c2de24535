"""The ``verify_inline`` reader: the share of each cold request's eager
bytes whose digest was taken inside their read stream, on synthetic traces,
nothing where the program keeps no traces or no such counter, and in a
traced run of the tiny cell, where every request verifies in its streams."""

import time

import numpy as np
import pytest

from bench import harness, loadgen

MS = 1_000_000  # ns


def _trace(fn, t0_ns, metrics):
    from repro.core.metrics import RequestTrace, Span

    t = RequestTrace("0:0")
    root = Span("request", {"fn": fn})
    root.start_ns, root.end_ns = t0_ns, t0_ns + 100 * MS
    t.spans.append(root)
    t.metrics = metrics
    return t


def _view(traces_at):
    records = []
    for k, (fn, t_s) in enumerate(traces_at):
        rec = loadgen.Record(index=k, function=fn, tokens=np.zeros((1, 4), np.int32))
        rec.submit, rec.done, rec.cold = t_s - 0.001, t_s + 0.101, True
        records.append(rec)
    return harness.View(records=records, t0=0.0, t_end=3.0, seconds=3.0, setup_s=0.0,
                        traffic={}, config={}, peaks=None, trace=None, patched_bytes={},
                        flops_per_request=0.0)


def _read(view):
    path = harness.BENCH_DIR / "metrics" / "verify_inline.py"
    return harness.load_module(path, "bench_metric_verify_inline").read(view)


def test_reads_the_mean_share_of_bytes_verified_in_their_streams(monkeypatch):
    import repro.core.metrics as metrics
    from repro.core.metrics import ColdStartMetrics

    traces = [_trace("a", 1000 * MS, ColdStartMetrics(eager_bytes=400,
                                                      inline_verified_bytes=400)),
              _trace("b", 2000 * MS, ColdStartMetrics(eager_bytes=400,
                                                      inline_verified_bytes=300)),
              # a demand-paged restore reads nothing eagerly: no share
              _trace("c", 2500 * MS, ColdStartMetrics(eager_bytes=0))]
    monkeypatch.setattr(metrics, "recent_traces", lambda: list(traces))
    assert _read(_view([("a", 1.0), ("b", 2.0), ("c", 2.5)])) == pytest.approx(87.5)


@pytest.mark.parametrize("program", ["no traces", "empty ring", "no counter"])
def test_finds_nothing_where_the_program_has_nothing(monkeypatch, program):
    import repro.core.metrics as metrics

    class Older:                     # a program's counters before in-stream hashing
        eager_bytes = 400
        recycled_bytes = 400

    view = _view([("a", 1.0)])
    if program == "no traces":
        monkeypatch.delattr(metrics, "recent_traces")
    elif program == "empty ring":
        monkeypatch.setattr(metrics, "recent_traces", lambda: [])
    else:
        monkeypatch.setattr(metrics, "recent_traces",
                            lambda: [_trace("a", 1000 * MS, Older())])
    assert _read(view) is None


def test_a_traced_tiny_run_verifies_inside_its_streams(tiny_root):
    cell = harness.load_cell(tiny_root, "stablelm3b.cold-rr")
    assert "verify_inline" in {m["name"] for m in cell.metrics("per_layer")}
    out = harness.run_cell(tiny_root, "stablelm3b.cold-rr", 2**31 + 13, 1.0, True,
                           process_start=time.perf_counter(), require_tpu=False,
                           cache=False, log=lambda row: None)
    assert out["correct"], out["checks"]
    assert out["metrics"]["verify_inline"]["value"] == pytest.approx(100.0)
