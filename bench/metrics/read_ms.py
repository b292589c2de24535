"""read_ms: mean length of the ``repro.restore.read`` span (the tier streams
of the eager restore: RAM memcpy, pack ``preadv``, the remote fetch, up to
verification) of the window's cold requests, in ms (program span)."""

from bench.spans import mean_ms


def read(view):
    return mean_ms(view, lambda t: t.total("repro.restore.read"))
