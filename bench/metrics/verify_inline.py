"""verify_inline: mean over the window's cold requests of the share of the
eager restore's bytes whose digest was taken inside their read stream, as
they landed (the program's counter ``ColdStartMetrics.inline_verified_bytes``
over ``eager_bytes``, carried on each request's trace), in %.  None where
the program keeps no such counter."""

from bench.spans import cold_traces


def read(view):
    shares = []
    for t in cold_traces(view) or ():
        m = t.metrics
        inline = getattr(m, "inline_verified_bytes", None)
        if inline is not None and m.eager_bytes:
            shares.append(inline / m.eager_bytes * 100.0)
    return sum(shares) / len(shares) if shares else None
