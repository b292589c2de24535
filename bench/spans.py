"""The program's own spans of the window's cold requests.

The program keeps the span tree of each of its last requests
(``repro.core.metrics.recent_traces``): ``repro.request`` around the whole
call and every step under it, on ``time.perf_counter_ns``, the clock the
load generator stamps ``submit`` and ``done`` with.  A request's trace is
the one of its function whose ``repro.request`` span lies inside the
record's ``[submit, done]``.  Where the program keeps no traces, the
readers built on this find nothing and return None.
"""

from __future__ import annotations


def cold_traces(view):
    """The trace of each cold request the window answered, in order;
    None where the program records none."""
    try:
        from repro.core.metrics import recent_traces
    except ImportError:
        return None
    found = []
    roots = [(t, t.spans[0]) for t in recent_traces()
             if t.spans and t.spans[0].name == "repro.request"]
    for r in view.ok:
        if not r.cold:
            continue
        lo, hi = r.submit * 1e9, r.done * 1e9
        for t, root in roots:
            if (root.meta.get("fn") == r.function
                    and lo <= root.start_ns and root.end_ns <= hi):
                found.append(t)
                break
    return found or None


def mean_ms(view, reading):
    """Mean over the window's cold requests of ``reading(trace)`` seconds,
    in ms; None without traces."""
    traces = cold_traces(view)
    if not traces:
        return None
    return sum(reading(t) for t in traces) / len(traces) * 1e3
