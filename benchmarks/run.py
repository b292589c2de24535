"""Benchmark entry point — one bench per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Figures covered:
Fig. 1 (CoW ratio), Fig. 5a–d (cold-start latencies), Fig. 6 (restored
bytes), Fig. 7 (throughput vs cold fraction), Table 2 (A/B/C/D breakdown +
Eq. 1 model validation), plus the §Roofline table from the dry-run.
"""

from __future__ import annotations

import sys
import traceback


def main() -> None:
    from repro.launch.cache import enable_compilation_cache

    from . import (
        bench_breakdown,
        bench_coldstart,
        bench_cow_ratio,
        bench_restored_bytes,
        bench_roofline,
        bench_throughput,
    )

    enable_compilation_cache()
    benches = [
        ("fig5_coldstart", bench_coldstart.run),
        ("table2_breakdown", bench_breakdown.run),
        ("fig6_restored_bytes", bench_restored_bytes.run),
        ("fig1_cow_ratio", bench_cow_ratio.run),
        ("fig7_throughput", bench_throughput.run),
        ("roofline", bench_roofline.run),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in benches:
        try:
            for line in fn():
                print(line, flush=True)
        except Exception:
            failures += 1
            print(f"{name},0,ERROR", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
