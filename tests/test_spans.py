"""Spans inside the request path: the cold span tree and its parents, strict
nesting, one request id per request, ``boot_s``/``exec_s`` as span lengths,
isolation between concurrent requests, and the same spans in the host plane
of a profiler trace."""

import glob
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro.core.metrics import RequestTrace, Span, recent_traces, span
from repro.serving import ColdStartOptions, InvocationRequest, Strategy

#: child -> parent of a planned snapfaas cold start through Worker.invoke
COLD_TREE = {
    "repro.boot": "repro.request",
    "repro.pool.drop": "repro.boot",
    "repro.restore.plan": "repro.boot",
    "repro.restore.alloc": "repro.boot",
    "repro.restore.eager": "repro.boot",
    "repro.restore.read": "repro.restore.eager",
    "repro.restore.verify": "repro.restore.eager",
    "repro.restore.init": "repro.boot",
    "repro.exec": "repro.request",
    "repro.exec.params": "repro.exec",
    "repro.exec.shapes": "repro.exec.params",
    "repro.exec.patch": "repro.exec.params",
    "repro.exec.forward": "repro.exec",
    "repro.pool.put": "repro.request",
}


def _build(tmp_path_factory, name, **kw):
    from repro.configs import get_config, reduced
    from repro.models import build_model
    from repro.serving.trace import build_cluster

    cfg = reduced(get_config("gemma-2b"))
    cluster, specs = build_cluster(str(tmp_path_factory.mktemp(name)), cfg,
                                   build_model(cfg), **kw)
    return cluster, specs, cfg


@pytest.fixture(scope="module")
def cluster_specs(tmp_path_factory):
    cluster, specs, cfg = _build(tmp_path_factory, "spans", n_workers=2,
                                 n_functions=3)
    yield cluster, specs, cfg
    cluster.shutdown()


def _req(spec, cfg, *, force_cold=True, seed=0):
    from repro.serving.trace import request_tokens

    toks = request_tokens(spec, np.random.default_rng(seed), cfg.vocab_size)
    return InvocationRequest(
        function=spec.name, tokens=toks,
        options=ColdStartOptions(strategy=Strategy.SNAPFAAS, force_cold=force_cold))


def _patched_spec(cluster, specs):
    """A function whose cold start patches a leaf on the device."""
    def patches(spec):
        plan = cluster.worker_for(spec.name).registry.restore_plan(spec.name, "snapfaas")
        return any(pa.patch_sel is not None for pa in plan.arrays)

    return next(spec for spec in specs if patches(spec))


def test_cold_span_tree_and_parents(cluster_specs):
    cluster, specs, cfg = cluster_specs
    r = cluster.invoke(_req(_patched_spec(cluster, specs), cfg))
    assert r.cold
    by_name = {}
    for s in r.trace.spans:
        by_name.setdefault(s.name, []).append(s)
    assert set(by_name) == set(COLD_TREE) | {"repro.request"}
    (root,) = by_name["repro.request"]
    assert root.parent is None and root.meta["fn"] == r.function
    for child, parent in COLD_TREE.items():
        for s in by_name[child]:
            assert s.parent is not None and s.parent.name == parent, (child, s.parent)
    assert all(s.meta["bytes"] > 0 for s in by_name["repro.exec.patch"])
    assert sum(s.meta["bytes"] for s in by_name["repro.restore.read"]) \
        == sum(r.metrics.tier_bytes.values())


def test_spans_nest_strictly(cluster_specs):
    cluster, specs, cfg = cluster_specs
    r = cluster.invoke(_req(specs[0], cfg))
    spans = r.trace.spans
    assert spans[0].name == "repro.request"
    assert [s.start_ns for s in spans] == sorted(s.start_ns for s in spans)
    for s in spans:
        assert 0 < s.start_ns <= s.end_ns
        if s.parent is not None:
            assert s.parent in spans
            assert s.parent.start_ns <= s.start_ns and s.end_ns <= s.parent.end_ns
    # siblings never overlap: the request path is one thread
    for s in spans:
        kids = sorted((c.start_ns, c.end_ns) for c in spans if c.parent is s)
        assert all(a[1] <= b[0] for a, b in zip(kids, kids[1:]))


def test_one_request_id_per_request(cluster_specs):
    cluster, specs, cfg = cluster_specs
    a = cluster.invoke(_req(specs[0], cfg, seed=1))
    b = cluster.invoke(_req(specs[0], cfg, seed=2))
    assert a.trace is not b.trace and a.trace.id != b.trace.id
    for r in (a, b):
        wid, n = r.trace.id.split(":")
        assert int(wid) == r.worker_id and int(n) >= 0
    assert not set(map(id, a.trace.spans)) & set(map(id, b.trace.spans))
    assert recent_traces()[-2:] == [a.trace, b.trace]


def test_boot_and_exec_are_span_lengths(cluster_specs):
    cluster, specs, cfg = cluster_specs
    r = cluster.invoke(_req(specs[1], cfg))
    t = r.trace
    assert r.boot_s == t.total("repro.boot") > 0
    assert r.exec_s == t.total("repro.exec") > 0
    m = r.metrics
    assert m.t_preconfig == t.total("repro.restore.alloc")
    assert m.t_eager == t.total("repro.restore.eager")
    assert m.t_init == t.total("repro.restore.init")
    assert m.boot_latency <= r.boot_s
    assert t.total("repro.request") >= r.latency_s


def test_warm_invoke_has_no_boot(cluster_specs):
    cluster, specs, cfg = cluster_specs
    cluster.invoke(_req(specs[2], cfg))
    r = cluster.invoke(_req(specs[2], cfg, force_cold=False))
    assert not r.cold and r.boot_s == 0.0
    names = {s.name for s in r.trace.spans}
    assert not {n for n in names if n.startswith("repro.restore")} | (names & {"repro.boot"})
    assert {"repro.request", "repro.exec", "repro.exec.params",
            "repro.exec.forward", "repro.pool.put"} <= names
    assert r.exec_s == r.trace.total("repro.exec")


def test_concurrent_requests_never_share_spans(tmp_path_factory):
    cluster, specs, cfg = _build(tmp_path_factory, "spans2", n_workers=1,
                                 n_functions=2, max_concurrency=2)
    try:
        for spec in specs:
            spec.exec_sleep_s = 0.3     # hold both requests inside exec
        futs = [cluster.submit(_req(spec, cfg)) for spec in specs]
        a, b = (f.result() for f in futs)
    finally:
        cluster.shutdown()
    ra, rb = a.trace.named("repro.request")[0], b.trace.named("repro.request")[0]
    assert ra.start_ns < rb.end_ns and rb.start_ns < ra.end_ns   # they overlapped
    assert a.trace.id != b.trace.id
    for t in (a.trace, b.trace):
        assert {s.meta.get("fn") for s in t.spans if s.name == "repro.request"} \
            == {t.spans[0].meta["fn"]}
        for s in t.spans:
            assert s.parent is None or s.parent in t.spans
    assert not set(map(id, a.trace.spans)) & set(map(id, b.trace.spans))


def test_spans_land_in_the_profilers_host_plane(cluster_specs, tmp_path):
    import jax
    from jax.profiler import ProfileData

    cluster, specs, cfg = cluster_specs
    jax.profiler.start_trace(str(tmp_path))
    try:
        r = cluster.invoke(_req(specs[0], cfg, seed=3))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    seen = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    stats = dict(e.stats)
                    if stats.get("req") == r.trace.id:
                        seen[e.name] = stats
    assert {s.name for s in r.trace.spans} == set(seen)
    assert seen["repro.request"]["fn"] == r.function
    assert seen["repro.restore.read"]["bytes"] > 0


# -- the recorder on its own ----------------------------------------------------


def _fake(name, start, end, parent=None):
    s = Span(name, {})
    s.start_ns, s.end_ns, s.parent = start, end, parent
    return s


def test_total_and_self_time():
    t = RequestTrace("0:0")
    p = _fake("params", 0, 1000)
    t.spans = [p, _fake("shapes", 100, 300, p), _fake("patch", 200, 500, p),
               _fake("patch", 700, 800, p)]
    assert t.total("repro.params") == pytest.approx(1e-6)
    assert t.total("repro.patch") == pytest.approx(0.4e-6)
    # children cover 100..500 and 700..800: 500 ns of 1000 left
    assert t.self_time("repro.params") == pytest.approx(0.5e-6)
    # a span nested in one of its own name is part of it
    read = _fake("read", 0, 100)
    t.spans = [read, _fake("read", 10, 60, read)]
    assert t.total("repro.read") == pytest.approx(0.1e-6)
    assert t.total("repro.missing") == 0.0 and t.self_time("repro.missing") == 0.0


def test_span_outside_a_request_is_timed_and_dropped():
    before = len(recent_traces())
    with span("restore.alloc") as s:
        time.sleep(0.001)
    assert s.seconds >= 0.001 and s.parent is None
    with RequestTrace("9:0") as t:
        with span("outer") as o:
            with span("inner") as i:
                pass
    assert t.spans == [o, i] and i.parent is o and o.parent is None
    assert len(recent_traces()) in (before + 1, before)   # the ring may be full
    assert recent_traces()[-1] is t


def test_traces_of_threads_stay_apart():
    out = {}

    def run(k):
        with RequestTrace(f"0:{k}") as t:
            for _ in range(50):
                with span("step"):
                    pass
        out[k] = t

    ths = [threading.Thread(target=run, args=(k,)) for k in range(4)]
    for th in ths:
        th.start()
    for th in ths:
        th.join()
    for t in out.values():
        assert len(t.spans) == 50 and all(s.parent is None for s in t.spans)


def test_core_imports_without_jax_until_the_first_span():
    code = ("import sys\n"
            "import repro.core\n"
            "from repro.core.metrics import span\n"
            "assert 'jax' not in sys.modules\n"
            "with span('boot') as s: pass\n"
            "assert 'jax.profiler' in sys.modules and s.end_ns >= s.start_ns\n"
            "print('ok')\n")
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                       env=dict(os.environ, PYTHONPATH=src), timeout=120)
    assert p.returncode == 0 and p.stdout.strip() == "ok", p.stderr
