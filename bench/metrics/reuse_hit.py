"""reuse_hit: mean over the window's cold requests of the share of the eager
restore's bytes read into destinations recycled from an earlier restore
(the program's counter ``ColdStartMetrics.recycled_bytes`` over
``eager_bytes``, carried on each request's trace), in %.  None where the
program keeps no such counter."""

from bench.spans import cold_traces


def read(view):
    shares = []
    for t in cold_traces(view) or ():
        m = t.metrics
        recycled = getattr(m, "recycled_bytes", None)
        if recycled is not None and m.eager_bytes:
            shares.append(recycled / m.eager_bytes * 100.0)
    return sum(shares) / len(shares) if shares else None
