"""ram_hit: mean over the window's cold requests of the share of the eager
restore's bytes the RAM tier served (the program's counter
``ColdStartMetrics.tier_bytes``, carried on each request's trace), in %."""

from bench.spans import cold_traces


def read(view):
    shares = []
    for t in cold_traces(view) or ():
        tiers = t.metrics.tier_bytes if t.metrics is not None else {}
        if sum(tiers.values()):
            shares.append(tiers.get("ram", 0) / sum(tiers.values()) * 100.0)
    return sum(shares) / len(shares) if shares else None
