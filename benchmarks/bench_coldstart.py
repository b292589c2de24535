"""Fig. 5 (a–d): cold-start boot / execution / end-to-end latency per
strategy, plus speed-up over `regular` and the optimal (warm) bound.

Also emits machine-readable results (``--json BENCH_coldstart.json``):
per-strategy A/B/D timings, restored bytes and eager-restore throughput
(restored bytes / t_eager), per-function ``auto`` rows (the Eq. 1 planner
picking the strategy at request time, compared against the best fixed
strategy), warm-pool policy rows (LRU / GDSF / TTL warm-hit rates on a
Zipf-skewed trace under a constrained budget), and a ``tiers`` section
(RAM-tier-warm restores vs pack-resident, plus a remote-bandwidth sweep
showing WS prefetch vs unprefetched remote-resident cold starts — the
paper's storage-bound regime) — the perf trajectory future PRs regress
against.
"""

from __future__ import annotations

import os
import tempfile
from collections import defaultdict
from typing import Dict, List, Optional

import numpy as np

from .common import (
    STRATEGIES,
    build_cluster_suite,
    build_delta_suite,
    build_suite,
    cold_request,
    csv_row,
    rounds,
    update_bench_json,
)

from repro.core.tiers import TierSpec
from repro.serving import (
    AdmissionConfig,
    InstancePool,
    Strategy,
    make_policy,
    make_requests,
    make_trace,
    zipf_schedule,
)


def _round_stats(rs) -> Dict[str, float]:
    med = lambda xs: float(np.median(xs))
    eager_bytes = int(np.median([r.metrics.eager_bytes for r in rs]))
    t_eager = med([r.metrics.t_eager for r in rs])
    return {
        "boot_s": med([r.boot_s for r in rs]),
        "exec_s": med([r.exec_s for r in rs]),
        "e2e_s": med([r.latency_s for r in rs]),
        "t_preconfig_s": med([r.metrics.t_preconfig for r in rs]),
        "t_eager_s": t_eager,
        "t_demand_s": med([r.metrics.t_demand for r in rs]),
        "t_cow_s": med([r.metrics.t_cow for r in rs]),
        "eager_bytes": eager_bytes,
        "demand_bytes": int(np.median([r.metrics.demand_bytes for r in rs])),
        "restored_GBps": (eager_bytes / t_eager / 1e9) if t_eager > 0 else 0.0,
    }


def _bench_tiers(root: str, n_functions: int, n_rounds: int):
    """Storage-hierarchy section: (a) RAM-tier-warm eager restores must not
    be slower than the pack path; (b) a remote-bandwidth sweep reproducing
    the paper's storage-bound regime — WS prefetch (registration-time
    promotion into the warm tiers) vs unprefetched remote-resident cold
    starts, which pay the throttled link inside the timed boot."""
    lines: List[str] = []
    # take the suite prefix up to "thumbnail" (head class, ~25 MB diff) so
    # real runs measure a storage-bound restore; quick CI runs keep 2
    n = max(2, min(5, n_functions))
    remote_lat = TierSpec().remote_lat
    payload: Dict[str, object] = {
        "config": {
            "n_functions": n, "n_rounds": n_rounds,
            "ram_bytes": 1 << 30, "remote_lat_s": remote_lat,
        },
        "remote_sweep": [],
    }

    # (a) warm-RAM-tier vs pack-resident eager restore (same worker: pack
    # rounds clear the RAM tier, ram rounds re-prefetch it after the drop)
    worker, specs = build_suite(
        os.path.join(root, "ram"), n_functions=n,
        tiers=TierSpec(ram_bytes=1 << 30),
    )
    spec = specs[-1]  # largest diff among the selected suite prefix
    pack = _round_stats(rounds(worker, spec, "snapfaas", n=n_rounds))
    ram_rs = []
    for r in range(n_rounds):
        worker.registry.store.drop_page_cache()
        worker.prefetch_function(spec.name)
        ram_rs.append(cold_request(worker, spec, "snapfaas",
                                   drop_cache=False, seed=300 + r))
    ram = _round_stats(ram_rs)
    ram_speedup = pack["t_eager_s"] / max(ram["t_eager_s"], 1e-9)
    payload["ram_vs_pack"] = {
        "function": spec.name,
        "pack": pack, "ram": ram,
        "pack_GBps": pack["restored_GBps"], "ram_GBps": ram["restored_GBps"],
        "ram_eager_speedup": ram_speedup,
        # acceptance: warm-RAM restore no slower than the pack engine
        # (1.25 tolerance absorbs scheduler noise at sub-ms eager times)
        "ram_no_slower": bool(ram["t_eager_s"] <= pack["t_eager_s"] * 1.25),
    }
    lines.append(csv_row(
        f"tiers_ram.{spec.name}", ram["t_eager_s"] * 1e6,
        f"pack_GBps={pack['restored_GBps']:.3f};"
        f"ram_GBps={ram['restored_GBps']:.3f};speedup={ram_speedup:.2f}x",
    ))

    # (b) remote-resident cold starts: bandwidth sweep, prefetch vs not.
    default_bw = TierSpec().remote_bw
    for bw in (150e6, default_bw):
        wroot = os.path.join(root, f"bw{int(bw/1e6)}")
        worker, specs = build_suite(
            wroot, n_functions=n,
            tiers=TierSpec(ram_bytes=1 << 30, remote_bw=bw,
                           remote_lat=remote_lat),
        )
        spec = specs[-1]
        moved = worker.registry.demote_function(spec.name)
        # unprefetched: every round restores straight from the throttled
        # remote (promote=False keeps the chunks remote-resident)
        nopre_rs = []
        for r in range(n_rounds):
            nopre_rs.append(cold_request(worker, spec, "snapfaas",
                                         seed=400 + r, promote=False))
        nopre = _round_stats(nopre_rs)
        # prefetched: the registration/shard-assignment promotion pays the
        # link once, off the timed path; cold starts then restore warm
        prefetch_stats = worker.prefetch_function(spec.name)
        pre_rs = []
        for r in range(n_rounds):
            worker.registry.store.drop_page_cache(clear_ram=False)
            pre_rs.append(cold_request(worker, spec, "snapfaas",
                                       drop_cache=False, seed=500 + r))
        pre = _round_stats(pre_rs)
        eager_speedup = nopre["t_eager_s"] / max(pre["t_eager_s"], 1e-9)
        boot_speedup = nopre["boot_s"] / max(pre["boot_s"], 1e-9)
        payload["remote_sweep"].append({
            "function": spec.name,
            "remote_bw_MBps": bw / 1e6,
            "default_bw": bw == default_bw,
            "demoted_bytes": moved,
            "noprefetch": nopre,
            "prefetch": pre,
            "prefetched_bytes": prefetch_stats.prefetched_bytes,
            "prefetch_remote_fetch_s": prefetch_stats.remote_fetch_s,
            "noprefetch_remote_fetch_s": float(np.median(
                [r.metrics.remote_fetch_s for r in nopre_rs])),
            "prefetch_eager_speedup": eager_speedup,
            "prefetch_boot_speedup": boot_speedup,
        })
        lines.append(csv_row(
            f"tiers_remote.{int(bw/1e6)}MBps", nopre["t_eager_s"] * 1e6,
            f"prefetch_eager_us={pre['t_eager_s']*1e6:.0f};"
            f"eager_speedup={eager_speedup:.2f}x;"
            f"boot_speedup={boot_speedup:.2f}x",
        ))
    return lines, payload


def _bench_dedup(root: str, n_functions: int, n_rounds: int):
    """Content-addressed dedup section: N functions born from ONE shared
    base via ``register_from_base``.

    (a) **bytes stored** — the CAS store (base once + per-function deltas)
        vs a flat baseline where each function's full snapshot is captured
        into its own per-function store (what per-function chunk keying
        costs).  Acceptance: CAS ≤ 0.5x flat for ≥ 4 functions.
    (b) **capture** — shared-base registration (delta scan + synthesized
        full manifest) vs the flat full-snapshot capture each function
        would otherwise pay.
    (c) **shared warm tier** — REAP cold starts where ONE sibling's
        ``ws_full`` prefetch RAM-warms the base-content digests every
        other sibling reads (residency is digest-keyed, not
        function-keyed), vs per-function caching (RAM cleared between
        functions).  Acceptance: a measured cold-e2e speedup.
    """
    import time as _time

    from repro.core.chunkstore import ChunkStore
    from repro.core.snapshot import take_snapshot

    n = max(4, min(6, n_functions))
    # the paper's storage-bound regime (same constrained point the tiers
    # remote sweep uses): a shared object-store link, not local NVMe
    remote_bw = 150e6
    lines: List[str] = []
    worker, specs, base_flat, reg_times = build_delta_suite(
        os.path.join(root, "cas"), n_functions=n,
        tiers=TierSpec(ram_bytes=1 << 30, remote_bw=remote_bw),
    )
    reg = worker.registry

    # (a)+(b): flat per-function baseline — every function captures its own
    # full snapshot into its own store; no cross-function index to dedup
    # against.  (The paper's premise: time redundancy ACROSS cold function
    # invocations exists — a per-function store can't exploit it.)
    flat_bytes = 0
    flat_capture_s = 0.0
    for i, spec in enumerate(specs):
        full_tree = dict(base_flat)
        full_tree.update(spec.delta)
        fstore = ChunkStore(os.path.join(root, "flat", spec.name))
        t0 = _time.perf_counter()
        take_snapshot(fstore, f"full-{spec.name}", full_tree,
                      chunk_bytes=256 * 1024)
        flat_capture_s += _time.perf_counter() - t0
        flat_bytes += fstore.stored_bytes()
        fstore.close()
    cas_bytes = reg.store.stored_bytes()
    base_bytes = reg.bases[specs[0].family].stored_bytes()
    ratio = cas_bytes / flat_bytes if flat_bytes else 1.0
    capture_speedup = flat_capture_s / max(sum(reg_times), 1e-9)
    lines.append(csv_row(
        "dedup.bytes_stored", cas_bytes / 1e6,
        f"flat_MB={flat_bytes/1e6:.1f};ratio={ratio:.3f};"
        f"capture_speedup={capture_speedup:.2f}x",
    ))

    # (c): shared warm tier vs per-function caching, for snapshots born on
    # another worker (the fleet case: functions land on a shard whose packs
    # don't hold them).  REAP reads the *full* snapshot from the store (no
    # base pool), so it is the strategy where digest-keyed residency pays
    # across siblings.  Every sibling's full snapshot is demoted behind the
    # throttled remote link; per-function caching then pays the link for
    # the WHOLE eager set on every function's cold start, while the shared
    # warm tier pays it once (one sibling's ws_full prefetch) and serves
    # the shared base-content digests to every other sibling from RAM —
    # each function still fetches its own delta remotely.
    sibs = specs[1:]
    cold_request(worker, specs[0], "reap", drop_cache=False)  # jit warmup
    demote_refs = {}
    for spec in specs:
        m = reg.functions[spec.name].full
        for a in m.arrays.values():
            for c in a.chunks:
                if c is not None and not c.zero:
                    demote_refs[c.digest] = c
    demoted = reg.store.demote(list(demote_refs.values()))
    per_fn_rs, shared_rs = [], []
    for r in range(n_rounds):
        # per-function caching baseline: RAM cleared before every cold
        # start, promote=False pins the chunks remote — nothing a sibling
        # fetched survives for the next function
        for spec in sibs:
            per_fn_rs.append(cold_request(worker, spec, "reap",
                                          clear_ram=True, seed=600 + r,
                                          promote=False))
    # shared warm tier: ONE prefetch of fn0's full-snapshot working set
    # pays the remote link off the timed path; every sibling's eager set
    # then hits RAM/local packs for the shared digests
    worker.registry.store.drop_page_cache(clear_ram=True)
    prefetch_stats = worker.prefetch_function(specs[0].name,
                                              category="ws_full")
    # promote=False: each sibling's own delta stays remote every round —
    # only the prefetch-warmed SHARED digests may be warm, so the speedup
    # measures digest sharing, not per-function caching sneaking back in
    for r in range(n_rounds):
        for spec in sibs:
            worker.registry.store.drop_page_cache(clear_ram=False)
            shared_rs.append(cold_request(worker, spec, "reap",
                                          drop_cache=False, seed=700 + r,
                                          promote=False))
    per_fn = _round_stats(per_fn_rs)
    shared = _round_stats(shared_rs)
    ram_hit_bytes = int(np.median(
        [r.metrics.tier_bytes.get("ram", 0) for r in shared_rs]
    ))
    e2e_speedup = per_fn["e2e_s"] / max(shared["e2e_s"], 1e-9)
    eager_speedup = per_fn["t_eager_s"] / max(shared["t_eager_s"], 1e-9)
    boot_speedup = per_fn["boot_s"] / max(shared["boot_s"], 1e-9)
    lines.append(csv_row(
        "dedup.shared_warm", shared["t_eager_s"] * 1e6,
        f"eager_speedup={eager_speedup:.2f}x;e2e_speedup={e2e_speedup:.2f}x;"
        f"ram_hit_MB={ram_hit_bytes/1e6:.1f}",
    ))

    payload = {
        "config": {"n_functions": n, "n_rounds": n_rounds,
                   "ram_bytes": 1 << 30, "remote_bw_MBps": remote_bw / 1e6,
                   "strategy": "reap"},
        "bytes_stored": {
            "cas_bytes": cas_bytes,
            "flat_bytes": flat_bytes,
            "base_bytes": base_bytes,
            "ratio": ratio,
            # acceptance: ≥4 functions sharing one base → CAS ≤ 0.5x flat
            "cas_at_most_half": bool(ratio <= 0.5),
        },
        "capture": {
            "register_from_base_s": sum(reg_times),
            "flat_full_capture_s": flat_capture_s,
            "speedup": capture_speedup,
        },
        "shared_warm": {
            "demoted_bytes": demoted,
            "prefetched_bytes": prefetch_stats.prefetched_bytes,
            "prefetch_remote_fetch_s": prefetch_stats.remote_fetch_s,
            "per_function_caching": per_fn,
            "shared_ram": shared,
            "ram_hit_bytes": ram_hit_bytes,
            "e2e_speedup": e2e_speedup,
            "eager_speedup": eager_speedup,
            "boot_speedup": boot_speedup,
        },
        "registry": reg.dedup_stats(),
    }
    return lines, payload


def _bench_trace_serving(root: str, n_functions: int, n_rounds: int):
    """Fleet-under-load section: the same seeded arrival traces replayed
    through the admission layer (bounded queues, concurrency caps, sheds)
    under the two scheduler configurations — the static-hash baseline and
    affinity placement + work stealing — plus one autoscaling run.

    Each comparison cell measures *steady-state* scheduling: pools are
    dropped, then an unmeasured warmup slice of the same arrival pattern
    (different seed) runs first, so both schedulers enter the measured
    window warm — the affinity side additionally enters with whatever
    thief residency its stealing earned, which is the feature under
    test.  Rows report the p50/p95/p99 end-to-end latency split into
    queueing delay vs cold boot vs execution, plus shed counts, steals
    and peak queue depths.  Three arrival shapes stress different
    things: ``poisson`` steady load, ``mmpp`` bursts (queue growth +
    sheds), ``diurnal`` a rate swing.  The ``acceptance`` block compares
    affinity+steal against the static baseline row per pattern
    (queueing-delay and shed cuts), and the ``autoscale`` row replays
    the MMPP trace — cold, no warmup: elasticity from a standing start
    is its story — on a 1-worker cluster that may grow to 4; its
    ``scale_events`` record the up/down decisions.

    Handlers are made I/O-bound (``FunctionSpec.exec_sleep_s``): real FaaS
    handlers mostly wait on downstream calls, and a GIL-releasing wait is
    what lets concurrent admission slots overlap on the small CI hosts this
    bench runs on.  Under compute-bound handlers a 1-core host serializes
    every slot, so total throughput — and therefore sheds — is identical
    for every scheduler by conservation; with wait-dominated service the
    load the static hash piles onto one shard (at 4 workers it leaves one
    worker with no functions at all, and the Zipf-hot function's shard
    sees ~1.7x its lane's service rate steadily, 4x+ during MMPP bursts)
    is load the other lanes could have absorbed — exactly what affinity
    placement and work stealing are for."""
    from repro.serving import AutoscaleConfig, InvocationRequest, StealConfig
    from repro.serving.trace import request_tokens
    from .common import BENCH_CFG

    n = max(3, min(4, n_functions))
    n_workers = 4
    # rps sized so the Zipf-hot function oversubscribes its *lane*
    # (~1.3x a 2-slot lane's service rate, more during bursts) while the
    # fleet keeps global slack (~0.65 utilization) — the regime where
    # scheduling matters: a static shard must shed what the idle lanes
    # could have absorbed
    rps, duration = 5.0, 12.0
    seed = 42
    exec_sleep_s, exec_seq = 1.0, 4
    adm = AdmissionConfig(queue_depth=6, worker_concurrency=2)
    # per-worker budget holds a worker's own function plus a stolen copy
    # of the hot one — this cell measures scheduling, not eviction churn
    # (the fig7 policy section owns that trade)
    budget = 512 << 20
    patterns = ("poisson", "mmpp", "diurnal")
    # min_depth=1: warm-steal as soon as anything queues — at a ~1s
    # service time a single queued request already costs more than a
    # warm steal.  Cold steals stay gated on a deep backlog (the boot's
    # CPU is a global cost on a small host, worth paying only to give a
    # sustained hot function a second warm home).
    steal_cfg = StealConfig(min_depth=1, min_cold_depth=3)
    schedulers = (
        {"name": "static", "placement": "static", "steal": None},
        {"name": "affinity_steal", "placement": "affinity",
         "steal": steal_cfg},
    )
    lines: List[str] = []
    rows: List[Dict[str, object]] = []

    def _replay_cell(cluster, specs, pattern, scheduler_name, *,
                     autoscale=None, warmup=True):
        for spec in specs:   # each cell begins from dropped pools
            for w in cluster.workers:
                w.pool.drop(spec.name)
        # diurnal: flatten the day/night swing so the *day peak* stays
        # within fleet capacity (the hot lane still oversubscribes ~1.7x
        # at peak) — with the default 1.8x peak the whole fleet is over
        # capacity at midday and every scheduler sheds alike.  mmpp:
        # soften the default 8x burst (23 rps — 3x the whole fleet's
        # service rate; a queue forms under any scheduler by
        # conservation) to 4x, which still slams the hot lane at ~7x its
        # service rate while the fleet as a whole can absorb the burst
        kw = {"depth": 0.4} if pattern == "diurnal" else (
            {"burst_factor": 4.0} if pattern == "mmpp" else {})
        if warmup:
            # unmeasured warmup slice: pays the cold starts and lets the
            # scheduler reach steady state (thieves warm for the hot
            # functions) before the measured window opens
            wtrace = make_trace(pattern, rps=rps, duration_s=5.0,
                                n_functions=len(specs), seed=seed + 1,
                                **kw)
            cluster.replay_trace(wtrace, specs, admission=adm,
                                 time_scale=1.0)
        h0 = sum(w.pool.hits for w in cluster.workers)
        m0 = sum(w.pool.misses for w in cluster.workers)
        trace = make_trace(pattern, rps=rps, duration_s=duration,
                           n_functions=len(specs), seed=seed, **kw)
        rep = cluster.replay_trace(trace, specs, admission=adm,
                                   autoscale=autoscale, time_scale=1.0)
        h1 = sum(w.pool.hits for w in cluster.workers)
        m1 = sum(w.pool.misses for w in cluster.workers)
        hits, misses = h1 - h0, m1 - m0
        row = {
            **rep.summary(),
            "policy": "lru",
            "scheduler": scheduler_name,
            "warm_hit_rate": round(hits / max(hits + misses, 1), 4),
        }
        rows.append(row)
        p99 = row["e2e_ms"].get("p99", 0.0)
        lines.append(csv_row(
            f"trace_serving.{pattern}.{scheduler_name}", p99 * 1e3,
            f"p99_queue_ms={row['queue_ms'].get('p99', 0.0)};"
            f"p99_cold_boot_ms={row['cold_boot_ms'].get('p99', 0.0)};"
            f"shed={row['n_shed']};cold={row['n_cold']};"
            f"steals={row['steals']};"
            f"warm_hit={row['warm_hit_rate']:.3f}",
        ))
        return row

    def _jit_warm(cluster, specs):
        # I/O-bound handler emulation (see docstring) + jit warm, off the
        # timed traces.  Mutating the registered spec objects is enough:
        # failover/scale-up re-registration reuses the same records.
        for spec in specs:
            spec.exec_seq = exec_seq
            spec.exec_sleep_s = exec_sleep_s
            toks = request_tokens(spec, np.random.default_rng(0),
                                  BENCH_CFG.vocab_size,
                                  seq=getattr(spec, "exec_seq", 32))
            cluster.invoke(InvocationRequest(function=spec.name,
                                             tokens=toks))

    for sched in schedulers:
        cluster, specs = build_cluster_suite(
            os.path.join(root, sched["name"]), n_functions=n,
            n_workers=n_workers,
            policy_factory=lambda: make_policy("lru"),
            pool_budget_bytes=budget,
            placement=sched["placement"], steal=sched["steal"],
            admission=adm,
        )
        with cluster:
            _jit_warm(cluster, specs)
            for pattern in patterns:
                _replay_cell(cluster, specs, pattern, sched["name"])

    # autoscale run: same MMPP trace, 1 worker elastically growing to 4 —
    # scale_events must show up during the bursts and down after them
    # high_depth must sit below the admission queue bound or the sampled
    # depth can never reach it; the intervals are sized to the ~1s service
    # time so one burst (not one request) moves the hysteresis counters
    autoscale_cfg = AutoscaleConfig(min_workers=1, max_workers=4,
                                    high_depth=3, low_depth=1,
                                    interval_s=0.25, up_after=2,
                                    down_after=4)
    cluster, specs = build_cluster_suite(
        os.path.join(root, "autoscale"), n_functions=n, n_workers=1,
        policy_factory=lambda: make_policy("lru"),
        pool_budget_bytes=budget,
        placement="affinity", steal=steal_cfg, admission=adm,
    )
    with cluster:
        _jit_warm(cluster, specs)
        autoscale_row = _replay_cell(cluster, specs, "mmpp", "autoscale",
                                     autoscale=autoscale_cfg,
                                     warmup=False)

    # acceptance: affinity+steal vs the static baseline, same seeds
    by_cell = {(r["pattern"], r["scheduler"]): r for r in rows}
    acceptance: Dict[str, object] = {"per_pattern": {}}
    queue_ok, shed_ok = [], []
    for pattern in patterns:
        base = by_cell[(pattern, "static")]
        new = by_cell[(pattern, "affinity_steal")]
        q_base = base["queue_ms"].get("p99", 0.0)
        q_new = new["queue_ms"].get("p99", 0.0)
        queue_cut = 1.0 - q_new / q_base if q_base else 0.0
        shed_cut = (1.0 - new["n_shed"] / base["n_shed"]
                    if base["n_shed"] else 0.0)
        acceptance["per_pattern"][pattern] = {
            "p99_queue_cut": round(queue_cut, 4),
            "shed_cut": round(shed_cut, 4),
        }
        queue_ok.append(queue_cut >= 0.30)
        shed_ok.append(shed_cut >= 0.20)
    scale_ups = [e for e in autoscale_row["scale_events"]
                 if e["action"] == "up"]
    scale_downs = [e for e in autoscale_row["scale_events"]
                   if e["action"] == "down"]
    acceptance.update({
        "p99_queue_cut_at_least_30pct": bool(all(queue_ok)),
        "shed_cut_at_least_20pct": bool(all(shed_ok)),
        "autoscale_scaled_up": bool(scale_ups),
        "autoscale_scaled_down": bool(scale_downs),
    })

    payload = {
        "config": {
            "n_functions": n, "n_workers": n_workers, "rps": rps,
            "duration_s": duration, "seed": seed, "time_scale": 1.0,
            "exec_sleep_s": exec_sleep_s, "exec_seq": exec_seq,
            "queue_depth": adm.queue_depth,
            "worker_concurrency": adm.worker_concurrency,
            "pool_budget_bytes": budget,
            "patterns": list(patterns),
            "policy": "lru",
            "schedulers": [s["name"] for s in schedulers] + ["autoscale"],
            "warmup_s": 5.0,
            "steal": {
                "min_depth": steal_cfg.min_depth,
                "min_cold_depth": steal_cfg.min_cold_depth,
                "max_cold_s": steal_cfg.max_cold_s,
            },
            "autoscale": {
                "min_workers": autoscale_cfg.min_workers,
                "max_workers": autoscale_cfg.max_workers,
                "high_depth": autoscale_cfg.high_depth,
                "low_depth": autoscale_cfg.low_depth,
            },
        },
        "rows": rows,
        "acceptance": acceptance,
    }
    return lines, payload


def _bench_chaos(root: str, n_functions: int, n_rounds: int):
    """Chaos section: the same seeded trace replayed through a fault-free
    cluster and through one under the standard fault matrix (1% corrupt
    reads, a remote-tier outage window, one worker crash mid-replay).

    The recovery machinery (verified reads + repair, retry/backoff, tier
    circuit breaking, worker failover) must contain the damage: request
    conservation holds, and the p99 end-to-end latency of *non-faulted*
    requests (completed without any recovery work on their path) stays
    within 1.5x of the fault-free baseline."""
    import threading as _threading

    from repro.core import FaultInjector, chaos_profile
    from repro.serving import percentiles

    # below saturation (2 workers x concurrency 2): p99 must reflect the
    # recovery path, not queue buildup amplifying every hiccup
    n = max(3, min(4, n_functions))
    rps, duration = 30.0, 2.5
    seed = 23
    profile = "standard"
    adm = AdmissionConfig(queue_depth=32, worker_concurrency=2)
    trace = make_trace("poisson", rps=rps, duration_s=duration,
                       n_functions=n, seed=seed)

    def _e2e(results, include_recovered):
        return [r.queue_s + r.latency_s for r in results
                if r is not None
                and (include_recovered or not r.fault_recovered)]

    # fault-free baseline row
    clean, specs = build_cluster_suite(
        os.path.join(root, "clean"), n_functions=n,
        tiers=TierSpec(ram_bytes=1 << 30),
    )
    with clean:
        clean_rep = clean.replay_trace(trace, specs, admission=adm,
                                       time_scale=1.0)
    baseline = percentiles(_e2e(clean_rep.results, True))

    # chaos run: one shared injector drives tier faults AND worker crashes
    injector = FaultInjector(chaos_profile(profile, seed=seed))
    chaos, cspecs = build_cluster_suite(
        os.path.join(root, "chaos"), n_functions=n,
        tiers=TierSpec(ram_bytes=1 << 30, faults=injector),
    )
    with chaos:
        # cold-restore under faults: demote every function so remote reads
        # (and the injected outage window) sit on the replay path
        for spec in cspecs:
            chaos.worker_for(spec.name).registry.demote_function(spec.name)
        down = _threading.Timer(0.1 * duration,
                                lambda: injector.fail_tier("remote"))
        heal = _threading.Timer(0.4 * duration,
                                lambda: injector.heal_tier("remote"))
        down.start()
        heal.start()
        try:
            rep = chaos.replay_trace(trace, cspecs, admission=adm,
                                     time_scale=1.0)
        finally:
            down.cancel()
            heal.cancel()
            injector.heal_tier("remote")
        m = chaos.metrics()

    nonfaulted = percentiles(_e2e(rep.results, False))
    p99_ratio = (
        round(nonfaulted["p99"] / baseline["p99"], 4)
        if nonfaulted.get("p99") and baseline.get("p99") else None
    )
    conservation = (
        rep.n_submitted == rep.n_completed + rep.n_shed + rep.n_failed
    )
    payload = {
        "config": {
            "profile": profile, "seed": seed, "n_functions": n,
            "n_workers": 2, "rps": rps, "duration_s": duration,
            "time_scale": 1.0, "queue_depth": adm.queue_depth,
            "worker_concurrency": adm.worker_concurrency,
            "outage_window_s": [0.1 * duration, 0.4 * duration],
        },
        "baseline": clean_rep.summary(),
        "chaos": rep.summary(),
        "baseline_e2e_ms": baseline,
        "nonfaulted_e2e_ms": nonfaulted,
        "p99_ratio": p99_ratio,
        # acceptance: recovery cost contained — non-faulted p99 within
        # 1.5x of the fault-free row (advisory on shared runners)
        "within_1_5x": bool(p99_ratio is not None and p99_ratio <= 1.5),
        "conservation_holds": bool(conservation),
        "failures": rep.failures(),
        "n_fault_recovered": rep.n_fault_recovered,
        "health": m["tiers"]["health"],
        "injected": m.get("chaos", {}),
        "n_worker_crashes": m["serving"]["n_worker_crashes"],
        "dead_workers": m["serving"]["dead_workers"],
    }
    ratio_txt = f"{p99_ratio:.2f}" if p99_ratio is not None else "n/a"
    lines = [csv_row(
        "chaos.nonfaulted_p99", nonfaulted.get("p99", 0.0) * 1e3,
        f"baseline_p99_ms={baseline.get('p99', 0.0)};ratio={ratio_txt};"
        f"recovered={rep.n_fault_recovered};failed={rep.n_failed};"
        f"crashes={payload['n_worker_crashes']};"
        f"conserved={int(conservation)}",
    )]
    return lines, payload


def _bench_demand_paging(root: str, n_functions: int, n_rounds: int):
    """Recorded working sets + demand-paged restore at the paper's 150 MBps
    storage-bound point.

    Two functions with the same ~25 MB diff (the whole embedding table is
    dirty) but opposite access patterns:

    * ``dp-small`` (small WS): execution gathers a 64-row band plus the
      logit slice — the REAP record phase projects to ~1 chunk of the diff,
      so a demand-paged cold start prefetches ~1% of what the eager full
      restore streams through the throttled link.
    * ``dp-full`` (full WS): the declared access pattern spans the whole
      table, so the recording covers ~everything — the regime where demand
      paging has nothing to elide and can only tie the eager stream.

    Modes per function, rounds paired by request seed: ``eager_full``
    (snapfaas-: the whole diff streamed eagerly — the eager-full-restore
    baseline), ``eager_ws`` (snapfaas: declared/measured WS eager) and
    ``demand`` (snapfaas demand-paged: background prefetch of the measured
    recording + lazy verified fault-in).  Every row carries the byte-
    equivalence flag against the eager-full output of the same round, the
    fault counters, and the conservation check
    ``prefetch == (demand - faults) + false_prefetch``.

    Acceptance (small-WS function): recorded set ≤ 25% of the snapshot,
    demand cold e2e ≤ 0.6x the eager full restore, zero demand faults on
    the second cold start, byte-identical outputs throughout."""
    import jax

    from repro.core.snapshot import flatten_pytree
    from repro.models import build_model
    from repro.serving import ColdStartOptions, InvocationRequest
    from repro.serving.trace import request_tokens
    from repro.serving.worker import FunctionSpec, Worker
    from .common import BENCH_CFG

    remote_bw = 150e6
    model = build_model(BENCH_CFG)
    worker = Worker(os.path.join(root, "worker"), chunk_bytes=256 * 1024,
                    tiers=TierSpec(ram_bytes=1 << 30, remote_bw=remote_bw),
                    prefetch_on_register=False)
    base_params = model.init(0)
    worker.register_runtime(BENCH_CFG.name, model, base_params)
    base_flat = flatten_pytree(jax.tree.map(np.asarray, base_params))
    rng = np.random.default_rng(17)

    band = list(range(64))
    small_table = np.array(base_flat["embed/table"]) * 1.01
    small_table[band] += 0.02 * rng.standard_normal(
        (len(band), small_table.shape[1])).astype(np.float32)
    small_variant = {k: np.array(v) for k, v in base_flat.items()}
    small_variant["embed/table"] = small_table
    small_spec = FunctionSpec(name="dp-small", family=BENCH_CFG.name,
                              variant=small_variant,
                              touched_rows={"embed/table": band})
    small_spec.exec_seq = 16  # type: ignore[attr-defined]

    full_variant = {k: np.array(v) for k, v in base_flat.items()}
    full_variant["embed/table"] = np.array(base_flat["embed/table"]) * 0.99
    full_spec = FunctionSpec(
        name="dp-full", family=BENCH_CFG.name, variant=full_variant,
        touched_rows={"embed/table": list(range(BENCH_CFG.vocab_size))})
    full_spec.exec_seq = 16  # type: ignore[attr-defined]
    for spec in (small_spec, full_spec):
        worker.register_function(spec)

    def _toks(spec, seed):
        return request_tokens(spec, np.random.default_rng(seed),
                              BENCH_CFG.vocab_size, batch=1,
                              seq=getattr(spec, "exec_seq", 32))

    def _cold(spec, strategy, seed, *, demand):
        # every measured round restores from the throttled remote: chunks
        # re-demoted (fault-in promotion and the background prefetch warm
        # them as a side effect) and the page cache dropped
        worker.registry.demote_function(spec.name)
        worker.registry.store.drop_page_cache(clear_ram=True)
        return worker.invoke(InvocationRequest(
            function=spec.name, tokens=_toks(spec, seed),
            options=ColdStartOptions(strategy=Strategy.coerce(strategy),
                                     force_cold=True, promote=False,
                                     demand_paging=demand),
        ))

    lines: List[str] = []
    rows: List[Dict[str, object]] = []
    acceptance: Dict[str, object] = {}
    auto_picks: Dict[str, bool] = {}
    for spec, ws_class in ((small_spec, "small_ws"), (full_spec, "full_ws")):
        # jit warm, then the REAP record phase (against local-resident
        # chunks: profiling is an un-timed, in-registration-flow step)
        worker.invoke(InvocationRequest(
            function=spec.name, tokens=_toks(spec, 0),
            options=ColdStartOptions(force_cold=True)))
        worker.record_function(spec.name, _toks(spec, 1), n_profiles=2)
        s = worker.registry.sizes(spec.name)
        recorded_frac = s.ws_bytes / max(s.diff_bytes, 1)
        worker.registry.demote_function(spec.name)
        auto_picks[spec.name] = worker.resolve_demand_paging(
            spec.name, ColdStartOptions(strategy=Strategy.AUTO))

        per_mode: Dict[str, tuple] = {}
        for mode, strategy, demand in (
            ("eager_full", "snapfaas-", False),
            ("eager_ws", "snapfaas", False),
            ("demand", "snapfaas", True),
        ):
            rs = [_cold(spec, strategy, 100 + r, demand=demand)
                  for r in range(n_rounds)]
            per_mode[mode] = (strategy, rs)
        ref = [np.asarray(r.output) for r in per_mode["eager_full"][1]]

        fn_rows: Dict[str, Dict[str, object]] = {}
        for mode, (strategy, rs) in per_mode.items():
            st = _round_stats(rs)
            faults = [int(r.metrics.demand_faults) for r in rs]
            demand_paged = bool(rs[0].metrics.demand_paged)
            row: Dict[str, object] = {
                "function": spec.name, "ws_class": ws_class,
                "strategy": strategy, "mode": mode, **st,
                "demand_paged": demand_paged,
                "demand_faults": int(np.median(faults)),
                "demand_faults_by_round": faults,
                "demand_fault_bytes": int(np.median(
                    [r.metrics.demand_fault_bytes for r in rs])),
                "prefetch_bytes": int(np.median(
                    [r.metrics.prefetch_bytes for r in rs])),
                "false_prefetch_bytes": int(np.median(
                    [r.metrics.false_prefetch_bytes for r in rs])),
                "recorded_frac": round(recorded_frac, 4),
                "byte_identical": bool(all(
                    np.array_equal(np.asarray(r.output), ref[i])
                    for i, r in enumerate(rs))),
                # prefetched bytes are either read (recorded hits) or
                # charged as false prefetch; reads outside are faults
                "conservation_ok": bool(all(
                    r.metrics.prefetch_bytes ==
                    (r.metrics.demand_bytes - r.metrics.demand_fault_bytes)
                    + r.metrics.false_prefetch_bytes
                    for r in rs)) if demand_paged else True,
            }
            rows.append(row)
            fn_rows[mode] = row

        d, ef = fn_rows["demand"], fn_rows["eager_full"]
        ratio = float(d["e2e_s"]) / max(float(ef["e2e_s"]), 1e-9)
        lines.append(csv_row(
            f"demand_paging.{ws_class}", float(d["e2e_s"]) * 1e6,
            f"eager_full_us={float(ef['e2e_s'])*1e6:.0f};"
            f"ratio={ratio:.2f};recorded_frac={recorded_frac:.3f};"
            f"faults={d['demand_faults']};"
            f"byte_identical={int(bool(d['byte_identical']))}",
        ))
        if ws_class == "small_ws":
            second = d["demand_faults_by_round"][1 if n_rounds > 1 else 0]
            acceptance = {
                "recorded_frac": round(recorded_frac, 4),
                "recorded_frac_le_25pct": bool(recorded_frac <= 0.25),
                "demand_vs_eager_full_e2e": round(ratio, 4),
                "demand_le_0_6x_eager_full": bool(ratio <= 0.6),
                "second_cold_demand_faults": int(second),
                "zero_faults_on_second_cold": bool(second == 0),
                "byte_identical": bool(all(
                    r["byte_identical"] for r in fn_rows.values())),
                "conservation_holds": bool(d["conservation_ok"]),
            }

    payload = {
        "config": {
            "n_rounds": n_rounds, "remote_bw_MBps": remote_bw / 1e6,
            "ram_bytes": 1 << 30, "chunk_bytes": 256 * 1024,
            "strategies": {"eager_full": "snapfaas-", "eager_ws": "snapfaas",
                           "demand": "snapfaas+demand"},
        },
        "rows": rows,
        "auto_picks_demand": auto_picks,
        "acceptance": acceptance,
    }
    return lines, payload


def run(
    n_functions: int = 6,
    n_rounds: int = 5,
    root: Optional[str] = None,
    json_path: Optional[str] = None,
) -> List[str]:
    n_rounds = max(1, n_rounds)
    root = root or tempfile.mkdtemp(prefix="bench_cold_")
    worker, specs = build_suite(root, n_functions=n_functions)
    lines: List[str] = []
    table: Dict[str, Dict[str, Dict[str, float]]] = defaultdict(dict)

    # optimal = warm execution only (paper Fig. 5d "optimal")
    from repro.serving import ColdStartOptions, InvocationRequest
    from repro.serving.trace import request_tokens
    from .common import BENCH_CFG
    for spec in specs:
        _ = cold_request(worker, spec, "snapfaas", drop_cache=False)
        toks = request_tokens(spec, np.random.default_rng(0), BENCH_CFG.vocab_size,
                              seq=getattr(spec, "exec_seq", 32))
        r_warm = worker.invoke(InvocationRequest(function=spec.name, tokens=toks))
        table[spec.name]["optimal"] = {"e2e_s": r_warm.exec_s}

    for strategy in STRATEGIES:
        for spec in specs:
            table[spec.name][strategy] = _round_stats(
                rounds(worker, spec, strategy, n=n_rounds)
            )

    for spec in specs:
        base = table[spec.name]
        sf = base["snapfaas"]["e2e_s"]
        for strategy in STRATEGIES:
            row = base[strategy]
            lines.append(csv_row(
                f"fig5_e2e.{strategy}.{spec.name}", row["e2e_s"] * 1e6,
                f"norm_to_snapfaas={row['e2e_s'] / sf:.2f};"
                f"boot_us={row['boot_s']*1e6:.0f};exec_us={row['exec_s']*1e6:.0f}",
            ))
        # Fig. 5d: speed-up over regular vs function exec time
        reg = base["regular"]["e2e_s"]
        opt = base["optimal"]["e2e_s"]
        lines.append(csv_row(
            f"fig5d_speedup.{spec.name}", base["snapfaas"]["e2e_s"] * 1e6,
            f"snapfaas={reg / base['snapfaas']['e2e_s']:.2f}x;"
            f"snapfaas-={reg / base['snapfaas-']['e2e_s']:.2f}x;"
            f"reap={reg / base['reap']['e2e_s']:.2f}x;"
            f"seuss={reg / base['seuss']['e2e_s']:.2f}x;"
            f"optimal={reg / opt:.2f}x",
        ))

    # Strategy.AUTO: the Eq. 1 planner picks per function at request time.
    # Acceptance: auto cold e2e ≤ the best fixed strategy (within noise).
    auto: Dict[str, Dict[str, object]] = {}
    for spec in specs:
        resolved = worker.resolve_strategy(spec.name, Strategy.AUTO).value
        fixed = {s: table[spec.name][s]["e2e_s"] for s in STRATEGIES}
        best_fixed = min(fixed, key=fixed.get)
        # paired rounds: auto and the best fixed strategy interleaved in the
        # same time window — section-ordering drift and the min-of-noisy-
        # medians bias otherwise dominate the few-ms boot differences
        cold_request(worker, spec, "auto", drop_cache=False)  # jit warm
        auto_rs, best_rs = [], []
        for r in range(n_rounds):
            auto_rs.append(cold_request(worker, spec, "auto", seed=200 + r))
            best_rs.append(cold_request(worker, spec, best_fixed,
                                        seed=200 + r))
        stats = _round_stats(auto_rs)
        best_stats = _round_stats(best_rs)
        auto[spec.name] = {
            **stats,
            "resolved": resolved,
            "best_fixed": best_fixed,
            "best_fixed_e2e_s": best_stats["e2e_s"],
            "auto_vs_best_fixed": stats["e2e_s"] / best_stats["e2e_s"],
            # boot is the strategy-controlled part of e2e (exec jitter
            # dominates e2e on shared CPU); report both comparisons
            "best_fixed_boot_s": best_stats["boot_s"],
            "auto_boot_vs_best_fixed":
                stats["boot_s"] / max(best_stats["boot_s"], 1e-9),
        }
        lines.append(csv_row(
            f"fig5_auto.{spec.name}", stats["e2e_s"] * 1e6,
            f"resolved={resolved};best_fixed={best_fixed};"
            f"ratio={stats['e2e_s'] / best_stats['e2e_s']:.2f}",
        ))

    # Warm-pool policy comparison on a Zipf-skewed trace under a budget that
    # holds ~45% of the suite (popularity rank = predicted re-boot cost, the
    # regime where cost-aware residency pays).  Acceptance: GDSF warm-hit
    # rate ≥ LRU's.
    by_cost = sorted(
        specs, key=lambda s: worker.predicted_cost(s.name, Strategy.SNAPFAAS),
        reverse=True,
    )
    # measure what the pool actually charges per instance (incl. the 2x for
    # patched device copies) with an unconstrained priming pass
    worker.pool = InstancePool(1 << 62)
    inst_bytes: Dict[str, int] = {}
    for spec in specs:
        toks = request_tokens(spec, np.random.default_rng(0),
                              BENCH_CFG.vocab_size,
                              seq=getattr(spec, "exec_seq", 32))
        worker.invoke(InvocationRequest(
            function=spec.name, tokens=toks,
            options=ColdStartOptions(strategy=Strategy.SNAPFAAS,
                                     force_cold=True),
        ))
        inst_bytes[spec.name] = worker.pool.size_of(spec.name)
    budget = max(int(sum(inst_bytes.values()) * 0.45),
                 max(inst_bytes.values()))
    schedule = zipf_schedule(max(12 * len(specs), 48), len(specs),
                             alpha=1.1, seed=7)
    policies: Dict[str, Dict[str, object]] = {}
    for name in ("lru", "gdsf", "ttl"):
        worker.pool = InstancePool(budget, policy=make_policy(name))
        results = [worker.invoke(req) for req in make_requests(
            by_cost, schedule, BENCH_CFG.vocab_size, strategy="snapfaas",
            seed=11,
        )]
        cold = [r for r in results if r.cold]
        stats = worker.pool.stats()
        policies[name] = {
            **stats,
            "n_requests": len(results),
            "n_cold": len(cold),
            "cold_e2e_s": float(np.mean([r.latency_s for r in cold]))
                          if cold else 0.0,
            "unpooled": sum(1 for r in results if not r.pooled),
        }
        lines.append(csv_row(
            f"fig7_policy.{name}", stats["warm_hit_rate"] * 1e6,
            f"warm_hit_rate={stats['warm_hit_rate']:.3f};"
            f"evictions={stats['evictions']};rejections={stats['rejections']};"
            f"n_cold={len(cold)}",
        ))

    # Storage-hierarchy section (fresh workers: the tier suites configure
    # their own RAM capacity and remote throttle).
    tier_lines, tiers_payload = _bench_tiers(
        os.path.join(root, "tiers"), n_functions, n_rounds
    )
    lines.extend(tier_lines)

    # Content-addressed dedup section (always ≥4 functions from one base,
    # whatever the main suite size — the acceptance bar needs the sharing).
    dedup_lines, dedup_payload = _bench_dedup(
        os.path.join(root, "dedup"), n_functions, n_rounds
    )
    lines.extend(dedup_lines)

    # Trace-driven serving section: seeded arrival traces through the
    # admission layer, 3 patterns × 2 scheduler configs (static vs
    # affinity+steal) plus an autoscaling run, percentile split.
    trace_lines, trace_payload = _bench_trace_serving(
        os.path.join(root, "trace"), n_functions, n_rounds
    )
    lines.extend(trace_lines)

    # Chaos section: standard fault matrix vs the fault-free baseline —
    # recovery cost and containment under injected faults.
    chaos_lines, chaos_payload = _bench_chaos(
        os.path.join(root, "chaos"), n_functions, n_rounds
    )
    lines.extend(chaos_lines)

    # Demand-paging section: recorded working sets vs eager restore at the
    # 150 MBps storage-bound point, with byte-equivalence and fault
    # conservation asserted per row.
    dp_lines, dp_payload = _bench_demand_paging(
        os.path.join(root, "demand"), n_functions, n_rounds
    )
    lines.extend(dp_lines)

    if json_path:
        update_bench_json(json_path, "coldstart", {
            "config": {"n_functions": n_functions, "n_rounds": n_rounds},
            "per_function": {k: dict(v) for k, v in table.items()},
            "auto": auto,
            "policies": {
                "config": {"budget_bytes": budget, "zipf_alpha": 1.1,
                           "n_requests": len(schedule)},
                **policies,
            },
            "tiers": tiers_payload,
            "dedup": dedup_payload,
            "trace_serving": trace_payload,
            "chaos": chaos_payload,
            "demand_paging": dp_payload,
        })
    return lines


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(
        description="cold-start latency bench (Fig. 5) + BENCH_coldstart.json"
    )
    ap.add_argument("--functions", type=int, default=6)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--json", default="BENCH_coldstart.json",
                    help="path of the machine-readable results file")
    args = ap.parse_args()
    for l in run(n_functions=args.functions, n_rounds=args.rounds,
                 json_path=args.json):
        print(l)
