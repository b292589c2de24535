"""Replay CLI: drive a seeded arrival trace through the multi-worker
cluster, optionally under an injected fault profile.

    PYTHONPATH=src python launch/serve.py --pattern poisson --rps 100
    PYTHONPATH=src python launch/serve.py --chaos remote-outage
    PYTHONPATH=src python launch/serve.py --chaos lossy-disk --chaos-seed 7

``--chaos`` wires a named fault profile (``remote-outage``, ``lossy-disk``,
``flaky-worker``, ``standard``) into the storage tiers and the worker
execution path via a seeded :class:`~repro.core.FaultInjector`; the same
(profile, seed) pair replays the same fault sequence.  The summary JSON
reports the typed failure taxonomy (shed / timeout / fault_recovered /
fault_fatal), tier-health counters (repairs, retries, breaker trips) and
the injected-fault counts next to the usual latency percentiles, so a
chaos run reads like a bench row.  Without ``--chaos`` the exit code is 1
when any replayed request failed; under a fault profile failures are the
expected outcome and are only reported.
"""

import argparse
import json
import sys
import tempfile

from repro.configs import get_config, reduced
from repro.core import CHAOS_PROFILES, FaultInjector, TierSpec, chaos_profile
from repro.launch.cache import enable_compilation_cache
from repro.models import build_model
from repro.serving import (
    AutoscaleConfig,
    StealConfig,
    make_trace,
    TRACE_PATTERNS,
)
from repro.serving.scheduler import PLACEMENTS
from repro.serving.trace import build_cluster


def parse_autoscale(value):
    """``MIN:MAX`` → :class:`AutoscaleConfig` (argparse type hook)."""
    try:
        lo, hi = value.split(":")
        return AutoscaleConfig(min_workers=int(lo), max_workers=int(hi))
    except (ValueError, TypeError):
        raise argparse.ArgumentTypeError(
            f"expected MIN:MAX (e.g. 1:4), got {value!r}"
        ) from None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="replay a seeded arrival trace through the cluster, "
                    "optionally under an injected fault profile"
    )
    ap.add_argument("--pattern", default="poisson", choices=TRACE_PATTERNS)
    ap.add_argument("--rps", type=float, default=100.0)
    ap.add_argument("--duration", type=float, default=2.0,
                    help="trace duration in seconds")
    ap.add_argument("--functions", type=int, default=4)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--strategy", default="snapfaas")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--time-scale", type=float, default=1.0,
                    help="arrival-time multiplier (0 = as fast as possible)")
    ap.add_argument("--chaos", default=None, choices=CHAOS_PROFILES,
                    metavar="PROFILE",
                    help=f"inject a named fault profile "
                         f"({', '.join(CHAOS_PROFILES)})")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="fault-injector seed (same seed → same faults)")
    ap.add_argument("--placement", default="static", choices=sorted(PLACEMENTS),
                    help="function→worker placement policy")
    ap.add_argument("--steal", action="store_true",
                    help="enable work stealing between admission lanes")
    ap.add_argument("--autoscale", type=parse_autoscale, default=None,
                    metavar="MIN:MAX",
                    help="autoscale the worker fleet between MIN and MAX "
                         "during the replay (starts at MIN)")
    ap.add_argument("--root", default=None,
                    help="cluster root (default: a fresh temp dir)")
    args = ap.parse_args(argv)

    enable_compilation_cache()
    injector = None
    tiers = TierSpec(ram_bytes=1 << 30)
    if args.chaos is not None:
        injector = FaultInjector(chaos_profile(args.chaos,
                                               seed=args.chaos_seed))
        tiers = TierSpec(ram_bytes=1 << 30, faults=injector)

    root = args.root or tempfile.mkdtemp(prefix="serve_replay_")
    cfg = reduced(get_config("gemma-2b"))
    model = build_model(cfg)
    n_workers = args.workers
    if args.autoscale is not None:
        n_workers = args.autoscale.min_workers
    cluster, specs = build_cluster(
        root, cfg, model, n_workers=n_workers,
        n_functions=args.functions, seed=args.seed, tiers=tiers,
        placement=args.placement,
        steal=StealConfig() if args.steal else None,
    )
    trace = make_trace(args.pattern, rps=args.rps, duration_s=args.duration,
                       n_functions=len(specs), seed=args.seed)
    with cluster:
        if injector is not None:
            # put cold restores on the faulted remote path, and re-arm the
            # profile's outage window (it counts from injector creation,
            # which registration would otherwise have used up)
            for spec in specs:
                cluster.worker_for(spec.name).registry.demote_function(
                    spec.name)
            injector.reset_clock()
        rep = cluster.replay_trace(trace, specs, strategy=args.strategy,
                                   autoscale=args.autoscale,
                                   time_scale=args.time_scale)
        metrics = cluster.metrics()

    out = {
        "summary": rep.summary(),
        "conservation_holds":
            rep.n_submitted == rep.n_completed + rep.n_shed + rep.n_failed,
        "tier_health": metrics["tiers"]["health"],
        "scheduler": metrics["scheduler"],
        "serving": {
            "failures": metrics["serving"]["failures"],
            "dead_workers": metrics["serving"]["dead_workers"],
            "n_worker_crashes": metrics["serving"]["n_worker_crashes"],
        },
    }
    if args.chaos is not None:
        out["chaos"] = {
            "profile": args.chaos,
            "seed": args.chaos_seed,
            "injected": metrics.get("chaos", {}),
        }
    print(json.dumps(out, indent=2, default=str))
    return 1 if rep.n_failed and args.chaos is None else 0


if __name__ == "__main__":
    sys.exit(main())
