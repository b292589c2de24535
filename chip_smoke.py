"""Smoke run of the cold-start serving path on one TPU.

    python chip_smoke.py

Serves stablelm-3b at its published widths, with the depth cut to 8 of its
32 layers and random weights from a seed, through the library's normal
path: ``build_cluster`` → ``Cluster`` → ``Worker.invoke`` → registry cold
start → device landing → the ``snapshot_patch`` kernel → the jitted
forward.  Two workers serve three functions (adapter, head, finetune).
For every strategy each function gets one forced-cold request and then one
warm request.  Then one function records its working set and cold-starts
demand-paged, and a short seeded poisson trace is replayed through the
admission layer.

Checks, each of which raises when it fails:

* the restored leaves are byte-equal to each function's variant, and so
  is every array the device patch produced;
* every output equals the logits of a never-snapshotted instance: the same
  forward function, jitted on its own, run on the variant params directly;
* the device patch fired on the snapfaas adapter and head cold starts;
* the replay failed no request and conserved submitted = completed + shed
  + failed;
* peak device memory stayed under the device's limit.

The script refuses to run without a TPU and never falls back to the CPU.
Times it prints come from single requests: smoke timings, not benchmark
numbers.  The last line of its output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import dataclasses
import json
import sys
import tempfile
import time
from pathlib import Path

MODEL = "stablelm-3b"
LAYERS = 8                 # of the published 32; every width is kept
N_WORKERS = 2
N_FUNCTIONS = 3            # adapter, head, finetune
STRATEGIES = ("regular", "reap", "seuss", "snapfaas-", "snapfaas", "auto")
SEED = 0
# host-side warm-pool accounting per worker: room for every function's
# instance, so each warm request really is warm
POOL_BUDGET_BYTES = 16 << 30
LOGITS_KEPT = 8            # InvocationResult.output keeps logits[:, -1, :8]


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def emit(**row) -> None:
    print(json.dumps(row, default=str), flush=True)


def smoke_config():
    from repro.configs import get_config

    return dataclasses.replace(get_config(MODEL), num_layers=LAYERS)


def _same_bytes(got, want) -> bool:
    import numpy as np

    got = np.ascontiguousarray(got)
    want = np.ascontiguousarray(want)
    return (got.dtype == want.dtype and got.shape == want.shape
            and np.array_equal(got.view(np.uint8), want.view(np.uint8)))


def check_restored(inst, spec) -> list:
    """Every leaf of the restored instance equals the variant byte for
    byte, on the host and, where the device patch ran, on the device.
    Returns the paths the device patch produced."""
    import numpy as np

    patched = []
    for path, want in spec.variant.items():
        ma = inst.arrays[path]
        if ma._dev is not None:
            patched.append(path)
            check(_same_bytes(np.asarray(ma._dev), want),
                  f"{spec.name}: device-patched {path} differs from the variant")
        check(_same_bytes(inst.value(path), want),
              f"{spec.name}: restored {path} differs from the variant")
    return sorted(patched)


def run(cfg, root: str, *, seed: int = SEED, replay_rps: float = 4.0,
        replay_s: float = 3.0) -> dict:
    """Drive the serving path at ``cfg`` and check every answer."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.snapshot import unflatten_paths
    from repro.models import Batch, build_model
    from repro.serving import (
        ColdStartOptions, InvocationRequest, build_cluster, make_trace,
    )
    from repro.serving.trace import request_tokens

    model = build_model(cfg)
    t0 = time.perf_counter()
    cluster, specs = build_cluster(
        root, cfg, model, n_workers=N_WORKERS, n_functions=N_FUNCTIONS,
        seed=seed, pool_budget_bytes=POOL_BUDGET_BYTES,
    )
    emit(phase="setup", build_cluster_s=time.perf_counter() - t0,
         functions=[s.name for s in specs])

    out = {"strategies": {}}
    with cluster:
        rng = np.random.default_rng(seed)
        tokens = {s.name: request_tokens(s, rng, cfg.vocab_size)
                  for s in specs}

        # never-snapshotted reference: the variant params put on the
        # device directly, through the same forward jitted on its own
        fwd = jax.jit(lambda p, t: model.logits(p, Batch(tokens=t)))
        compiled = None
        ref = {}
        for spec in specs:
            params = jax.device_put(unflatten_paths(spec.variant))
            toks = jnp.asarray(tokens[spec.name])
            if compiled is None:
                tl = time.perf_counter()
                lowered = fwd.lower(params, toks)
                tc = time.perf_counter()
                compiled = lowered.compile()  # loads it on a persistent-cache hit
                out["first_compile_s"] = time.perf_counter() - tc
                emit(phase="compile", lower_s=tc - tl,
                     first_compile_s=out["first_compile_s"],
                     shape=list(toks.shape))
            ref[spec.name] = np.asarray(
                compiled(params, toks)[:, -1, :LOGITS_KEPT])
            check(bool(np.isfinite(ref[spec.name]).all()),
                  f"{spec.name}: reference logits are not finite")
            del params

        def invoke(fn, **opts):
            return cluster.invoke(InvocationRequest(
                function=fn, tokens=tokens[fn],
                options=ColdStartOptions(**opts)))

        def check_output(res, what):
            check(np.array_equal(res.output, ref[res.function]),
                  f"{what}: logits differ from the never-snapshotted instance")

        for strategy in STRATEGIES:
            for spec in specs:
                fn = spec.name
                cold = invoke(fn, strategy=strategy, force_cold=True)
                check(cold.cold, f"{strategy} {fn}: forced-cold request ran warm")
                inst = cluster.worker_for(fn).pool.get(fn)
                check(inst is not None,
                      f"{strategy} {fn}: the cold instance was not kept warm")
                warm = invoke(fn, strategy=strategy)
                check(not warm.cold, f"{strategy} {fn}: warm request ran cold")
                check_output(cold, f"{strategy} {fn} cold")
                check_output(warm, f"{strategy} {fn} warm")
                patched = check_restored(inst, spec)
                if strategy == "snapfaas" and not fn.endswith("-finetune"):
                    check("embed/table" in patched,
                          f"snapfaas {fn}: the device patch did not fire")
                row = dict(
                    strategy=strategy, resolved=str(cold.strategy),
                    function=fn, worker=cold.worker_id,
                    cold_boot_s=cold.boot_s, cold_exec_s=cold.exec_s,
                    warm_exec_s=warm.exec_s, device_patched=patched,
                    eager_bytes=cold.metrics.eager_bytes,
                )
                out["strategies"].setdefault(strategy, []).append(row)
                emit(phase="strategy", **row)

        # REAP-style record, then a demand-paged cold start from it
        spec = specs[0]
        fn = spec.name
        tr = time.perf_counter()
        rec = cluster.record_function(fn, tokens[fn])
        record_s = time.perf_counter() - tr
        check_output(rec, f"record {fn}")
        dp = invoke(fn, strategy="snapfaas", force_cold=True,
                    demand_paging=True)
        check(dp.cold and dp.metrics is not None and dp.metrics.demand_paged,
              f"{fn}: the demand-paged request did not cold-start demand-paged")
        check_output(dp, f"demand-paged {fn}")
        check_restored(cluster.worker_for(fn).pool.get(fn), spec)
        out["demand_paging"] = dict(
            function=fn, record_s=record_s, cold_boot_s=dp.boot_s,
            cold_exec_s=dp.exec_s, demand_faults=dp.metrics.demand_faults,
            prefetch_bytes=dp.metrics.prefetch_bytes,
        )
        emit(phase="demand_paging", **out["demand_paging"])

        trace = make_trace("poisson", rps=replay_rps, duration_s=replay_s,
                           n_functions=len(specs), seed=seed)
        rep = cluster.replay_trace(trace, specs, strategy="snapfaas")
        check(rep.n_failed == 0,
              f"replay: {rep.n_failed} failed requests: {rep.errors[:3]}")
        check(rep.n_submitted == rep.n_completed + rep.n_shed + rep.n_failed,
              "replay: submitted != completed + shed + failed")
        check(rep.n_completed > 0, "replay: no request completed")
        for res in rep.completed():
            check(bool(np.isfinite(res.output).all()),
                  f"replay {res.function}: logits are not finite")
        summary = rep.summary()
        out["replay"] = {k: summary[k] for k in (
            "pattern", "n_submitted", "n_completed", "n_shed", "n_failed",
            "n_cold", "wall_s", "e2e_ms", "exec_ms")}
        emit(phase="replay", **out["replay"])
    return out


def main() -> int:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}; "
              "refusing to run elsewhere", file=sys.stderr)
        return 1
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.cache import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    emit(phase="device", **device)
    emit(phase="cache", compilation_cache_dir=cache_dir)
    emit(phase="note", timings="single requests on one chip: smoke "
         "timings, not benchmark numbers")

    from repro.configs import get_config

    published = get_config(MODEL).num_layers
    cfg = smoke_config()
    emit(phase="config", model=MODEL, layers=cfg.num_layers,
         published_layers=published,
         cut=f"depth only: {published} -> {cfg.num_layers} layers",
         d_model=cfg.d_model, num_heads=cfg.num_heads,
         num_kv_heads=cfg.num_kv_heads, d_ff=cfg.d_ff,
         vocab_size=cfg.vocab_size, norm=cfg.norm,
         tie_embeddings=cfg.tie_embeddings, dtype=cfg.dtype,
         params=cfg.param_count(), weights=f"random, seed {SEED}")

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as root:
        run(cfg, root)

    stats = dev.memory_stats() or {}
    peak, limit = stats.get("peak_bytes_in_use"), stats.get("bytes_limit")
    emit(phase="memory", peak_bytes_in_use=peak, bytes_limit=limit)
    check(peak is not None and limit is not None and peak < limit,
          "peak device memory is not reported, or reached the limit")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
