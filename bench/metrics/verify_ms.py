"""verify_ms: mean length of the ``repro.restore.verify`` span (BLAKE2b over
every chunk the eager restore filled, with repair) of the window's cold
requests, in ms (program span)."""

from bench.spans import mean_ms


def read(view):
    return mean_ms(view, lambda t: t.total("repro.restore.verify"))
