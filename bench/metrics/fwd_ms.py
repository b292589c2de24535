"""fwd_ms: mean length of the ``repro.exec.forward`` span (the forward's
dispatch through ``block_until_ready``, so every device op still queued)
of the window's cold requests, in ms (program span)."""

from bench.spans import mean_ms


def read(view):
    return mean_ms(view, lambda t: t.total("repro.exec.forward"))
