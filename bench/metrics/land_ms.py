"""land_ms: mean self time of the ``repro.exec.params`` span of the window's
cold requests, in ms (program span): building the request's parameters
outside its ``repro.exec.shapes`` and ``repro.exec.patch`` children, i.e.
host materialization and the enqueueing of host leaves to the device."""

from bench.spans import mean_ms


def read(view):
    return mean_ms(view, lambda t: t.self_time("repro.exec.params"))
