"""alloc_ms: mean length of the ``repro.restore.alloc`` span (the restore's
A phase: private buffer allocation and wiring) of the window's cold
requests, in ms (program span)."""

from bench.spans import mean_ms


def read(view):
    return mean_ms(view, lambda t: t.total("repro.restore.alloc"))
