"""Cold-start metrics: the A/B/C/D breakdown of the paper's Table 2.

    T_cold = max(c, bytes_unique / bw_store) + init + faults_shared · lat_mem
             └──A──┘ └────────B────────────┘  └─C─┘  └──────────D──────────┘

A — instance pre-configuration (buffer allocation, device-state restore)
B — eager restoration from storage (batched, bandwidth-bound)
C — residual, un-memoizable initialization (KV alloc, RNG, channels)
D — execution-time slowdown: demand-paged chunks + copy-on-write faults

Spans
-----
:func:`span` times one step of the request path on the host clock
(``time.perf_counter_ns``) and, at the same time, enters
``jax.profiler.TraceAnnotation("repro.<name>", req=<id>, **meta)``: under a
running profiler the step lands in the host plane of the device trace, on
the device trace's clock, next to the device ops it enqueued.  Inside a
request (a :class:`RequestTrace` entered by ``Worker.invoke``) the span is
kept in that request's trace with its parent, the enclosing span of the
same request; outside one it is timed and dropped.  The A/B/C fields of
:class:`ColdStartMetrics` are the lengths of the ``repro.restore.*`` spans,
so there is one measurement, not two.

Dispatch is asynchronous: a span around ``jnp.asarray`` or a kernel call
measures host work and enqueueing only, and the span that ends in
``block_until_ready`` absorbs the device time queued before it.  The device
side of each step is read from the device trace.
"""

from __future__ import annotations

import collections
import contextvars
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional


@dataclass
class ColdStartMetrics:
    strategy: str = ""
    function: str = ""
    # A: pre-configuration
    t_preconfig: float = 0.0
    # B: eager restore
    t_eager: float = 0.0
    eager_bytes: int = 0
    eager_chunks: int = 0
    # content-addressed dedup: bytes actually read after collapsing
    # duplicate digests (the scatter-read engine reads each digest once,
    # however many chunks reference it); equals eager_bytes when the eager
    # set shares nothing with itself
    eager_unique_bytes: int = 0
    # eager bytes read into destinations recycled from an earlier restore
    # (pages already mapped: no first-touch faults)
    recycled_bytes: int = 0
    # eager bytes whose digest was taken inside their read stream, while
    # still in cache, rather than by the pass after every stream landed
    inline_verified_bytes: int = 0
    # C: residual init
    t_init: float = 0.0
    # D: execution-time restoration overhead
    t_demand: float = 0.0
    demand_bytes: int = 0
    demand_chunks: int = 0
    t_cow: float = 0.0
    cow_faults: int = 0
    cow_bytes: int = 0
    # extra bookkeeping
    shared_bytes_mapped: int = 0  # base bytes served from the in-RAM pool
    # tier breakdown of the B phase (tiered stores only): which storage tier
    # served how much of the eager set, remote-link time, and bytes promoted
    # downward as a side effect of this restore
    tier_chunks: Dict[str, int] = field(default_factory=dict)
    tier_bytes: Dict[str, int] = field(default_factory=dict)
    remote_fetch_s: float = 0.0
    promoted_bytes: int = 0
    # recovery work the B phase absorbed (fault injection / real faults):
    # tier-read retries beyond the first attempt, and chunks healed from
    # another tier or a shared base after a failed or corrupt read
    read_retries: int = 0
    repaired_chunks: int = 0
    # demand-paged restore (REAP-style record-and-prefetch): the recorded
    # set is prefetched in the background while execution starts; chunks the
    # recording *missed* fault in on first access, and recorded chunks the
    # execution never touched were prefetched for nothing
    demand_paged: bool = False
    prefetch_bytes: int = 0
    demand_faults: int = 0
    demand_fault_bytes: int = 0
    false_prefetch_bytes: int = 0

    @property
    def boot_latency(self) -> float:
        """VMM-start → ready-to-accept (Fig. 5a)."""
        return self.t_preconfig + self.t_eager + self.t_init

    @property
    def d_overhead(self) -> float:
        return self.t_demand + self.t_cow


# -- spans ----------------------------------------------------------------------

#: finished request traces kept for inspection without a profiler (the last
#: few hundred requests of the process, oldest dropped first)
RECENT_TRACES = 1024

_TRACE: contextvars.ContextVar[Optional["RequestTrace"]] = contextvars.ContextVar(
    "repro_request_trace", default=None)
_PARENT: contextvars.ContextVar[Optional["Span"]] = contextvars.ContextVar(
    "repro_parent_span", default=None)
_RECENT: "collections.deque[RequestTrace]" = collections.deque(maxlen=RECENT_TRACES)
_TraceAnnotation: Optional[Callable[..., Any]] = None


def _annotation(name: str, stats: Dict[str, Any]) -> Any:
    """``jax.profiler.TraceAnnotation(name, **stats)``, imported at the
    first span so that ``repro.core`` itself imports no JAX."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation as _TraceAnnotation
    return _TraceAnnotation(name, **stats)


class Span:
    """One timed step: ``name`` (``repro.``-prefixed), ``start_ns`` and
    ``end_ns`` on ``time.perf_counter_ns``, the enclosing span of the same
    request as ``parent`` (None at the top or outside a request), and
    ``meta`` (e.g. ``bytes``), which is also the profiler event's stats."""

    __slots__ = ("name", "meta", "parent", "start_ns", "end_ns",
                 "_trace", "_token", "_ann")

    def __init__(self, name: str, meta: Dict[str, Any]) -> None:
        self.name = "repro." + name
        self.meta = meta
        self.parent: Optional[Span] = None
        self.start_ns = self.end_ns = 0

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9

    def __enter__(self) -> "Span":
        trace = self._trace = _TRACE.get()
        stats = self.meta
        if trace is not None:
            self.parent = _PARENT.get()
            self._token = _PARENT.set(self)
            trace.spans.append(self)
            stats = dict(stats, req=trace.id)
        self._ann = _annotation(self.name, stats)
        self._ann.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.end_ns = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        if self._trace is not None:
            _PARENT.reset(self._token)
        self._trace = self._ann = None


def span(name: str, **meta: Any) -> Span:
    """``with span("restore.read", bytes=n) as s:`` — time the block as
    ``repro.restore.read`` (see the module docstring); ``s.seconds`` is its
    length once the block has left."""
    return Span(name, meta)


class RequestTrace:
    """The spans of one request, in the order they started.  Entered by
    ``Worker.invoke`` around the whole call (``id`` is
    ``"<worker_id>:<n>"``); every :func:`span` opened in that context
    while it is entered lands here.  On exit the trace joins
    :func:`recent_traces`."""

    def __init__(self, id: str) -> None:
        self.id = id
        self.spans: List[Span] = []
        #: the counters of the request's cold start (None on a warm hit)
        self.metrics: Optional[ColdStartMetrics] = None

    def __enter__(self) -> "RequestTrace":
        self._tokens = (_TRACE.set(self), _PARENT.set(None))
        return self

    def __exit__(self, *exc) -> None:
        _PARENT.reset(self._tokens[1])
        _TRACE.reset(self._tokens[0])
        _RECENT.append(self)

    def named(self, name: str) -> List[Span]:
        """The outermost spans called ``name``: a span nested in another
        of the same name (a re-dispatched read) is part of its ancestor."""
        def nested(s: Span) -> bool:
            p = s.parent
            while p is not None:
                if p.name == name:
                    return True
                p = p.parent
            return False

        return [s for s in self.spans if s.name == name and not nested(s)]

    def total(self, name: str) -> float:
        """Seconds spent in spans called ``name`` (outermost ones only)."""
        return sum(s.seconds for s in self.named(name))

    def self_time(self, name: str) -> float:
        """Seconds spent in spans called ``name`` outside every child span:
        each span's length less the union of its children's intervals."""
        out = 0
        for s in self.named(name):
            kids = sorted((c.start_ns, c.end_ns) for c in self.spans if c.parent is s)
            covered, reach = 0, s.start_ns
            for a, b in kids:
                a = max(a, reach)
                if b > a:
                    covered += b - a
                    reach = b
            out += (s.end_ns - s.start_ns) - covered
        return out / 1e9


def recent_traces() -> List[RequestTrace]:
    """The last :data:`RECENT_TRACES` finished request traces, oldest first."""
    return list(_RECENT)
