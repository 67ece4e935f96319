"""One facade over the library's introspection surfaces.

Five subsystems keep counters that benchmarks and the gated lanes stamp
into JSON: the top-M pre-filter (:func:`repro.core.prefilter.stats`),
the EC coefficient-matrix caches
(:func:`repro.kernels.ops.matrix_cache_stats`), the shape-bucketer
compile census (:func:`repro.core.shapes.compile_cache_stats`), the
per-engine :class:`~repro.core.engine.PlacementEngine` decision counters
(``engine.stats``), and the persistent XLA compilation cache
(:func:`repro.core.jitcache.status`).  Importing each module ad hoc
couples every benchmark to four internal layouts; this facade freezes one stable
schema (:class:`TelemetrySnapshot`) behind :func:`snapshot` /
:func:`reset`.

The leaf dictionaries are byte-compatible with what the underlying
surfaces emit (the facade copies, it does not reshape), so benchmark
JSON stamped through ``snapshot()`` is identical to what the ad-hoc
imports produced — no baseline churn.

The first three surfaces are process-wide; engine counters live on each
:class:`PlacementEngine` instance, so ``snapshot(engine=...)`` takes the
instance to read (``engine=None`` in the snapshot otherwise), and
:func:`reset` only touches the process-wide state.

Spans (:func:`span`) time the layers of a request on the host: each one
adds its count, seconds, self seconds (its duration less that of the
spans nested in it on the same thread) and the bytes it wrote into new
buffers to process-wide totals, and to the totals of its request (a
checkpoint save's step) for the last :data:`REQUESTS_KEPT` requests.
The totals are always on and cost a few microseconds a span.
Counters (:func:`count`) add a number under a name to the same
process-wide and per-request totals, where the request is the one of
the span open on the calling thread (or given explicitly).  Full
span records (:class:`SpanRecord`) exist only while a listener is
registered (:func:`add_span_listener`), and go to the listener alone.
Each span also opens a ``jax.profiler.TraceAnnotation`` of its name, so
a profile taken with host tracing on shows it beside the device ops.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import threading
import time
from typing import Any, Callable, Hashable, Iterator, Optional

import jax

__all__ = [
    "TelemetrySnapshot",
    "snapshot",
    "reset",
    "span",
    "span_stats",
    "count",
    "SpanRecord",
    "add_span_listener",
    "remove_span_listener",
    "REQUESTS_KEPT",
]

#: requests whose per-request span totals are kept (oldest dropped first).
REQUESTS_KEPT = 64


@dataclasses.dataclass(frozen=True)
class SpanRecord:
    """One closed span, as handed to the span listeners."""

    name: str
    #: ``time.perf_counter_ns()`` at entry and at exit.
    start_ns: int
    end_ns: int
    #: process-unique id of this span and of the span it is nested in on
    #: the same thread (``None`` at the top of the thread's stack).
    span_id: int
    parent_id: Optional[int]
    #: ``threading.get_ident()`` of the thread that ran it.
    thread: int
    #: the request it belongs to: given explicitly, else its parent's.
    request: Optional[Hashable]
    nbytes: int


class _Open:
    """A span while it runs; ``with span(...) as sp`` yields it, so the
    body may add to ``sp.nbytes`` and read ``sp.seconds`` after exit."""

    __slots__ = ("nbytes", "request", "span_id", "parent", "child_ns", "start_ns", "end_ns")

    def __init__(self, nbytes, request, span_id, parent):
        self.nbytes, self.request, self.span_id, self.parent = nbytes, request, span_id, parent
        self.child_ns = 0
        self.start_ns = self.end_ns = 0

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


_span_lock = threading.Lock()
_span_ids = itertools.count(1)
_span_stack = threading.local()
#: name -> [count, ns, self ns, bytes], over the whole process.
_span_totals: dict[str, list[int]] = {}
#: name -> summed count, over the whole process.
_counter_totals: dict[str, int] = {}
#: request -> ({span name -> [count, ns, self ns, bytes]}, {counter name ->
#: summed count}), oldest request first.
_request_totals: collections.OrderedDict = collections.OrderedDict()
_span_listeners: list[Callable[[SpanRecord], None]] = []


def _request(request: Hashable) -> tuple[dict, dict]:
    """The request's (spans, counters) totals, made if new (oldest dropped
    beyond :data:`REQUESTS_KEPT`); call under ``_span_lock``."""
    per = _request_totals.get(request)
    if per is None:
        per = _request_totals[request] = ({}, {})
        while len(_request_totals) > REQUESTS_KEPT:
            _request_totals.popitem(last=False)
    return per


def _add(totals: dict, name: str, dur_ns: int, self_ns: int, nbytes: int) -> None:
    t = totals.setdefault(name, [0, 0, 0, 0])
    t[0] += 1
    t[1] += dur_ns
    t[2] += self_ns
    t[3] += nbytes


@contextlib.contextmanager
def span(name: str, nbytes: int = 0, *, request: Optional[Hashable] = None) -> Iterator[_Open]:
    """Time the block as span ``name``; ``nbytes`` (more may be added to
    the yielded span) counts the bytes it writes into new buffers.
    Work handed to another thread passes ``request`` explicitly."""
    stack = getattr(_span_stack, "open", None)
    if stack is None:
        stack = _span_stack.open = []
    parent = stack[-1] if stack else None
    if request is None and parent is not None:
        request = parent.request
    sp = _Open(nbytes, request, next(_span_ids), parent)
    stack.append(sp)
    try:
        with jax.profiler.TraceAnnotation(name):
            sp.start_ns = time.perf_counter_ns()
            try:
                yield sp
            finally:
                sp.end_ns = time.perf_counter_ns()
    finally:
        stack.pop()
        dur = sp.end_ns - sp.start_ns
        if parent is not None:
            parent.child_ns += dur
        with _span_lock:
            _add(_span_totals, name, dur, dur - sp.child_ns, sp.nbytes)
            if request is not None:
                _add(_request(request)[0], name, dur, dur - sp.child_ns, sp.nbytes)
            listeners = tuple(_span_listeners)
        if listeners:
            rec = SpanRecord(name, sp.start_ns, sp.end_ns, sp.span_id,
                             parent.span_id if parent is not None else None,
                             threading.get_ident(), request, sp.nbytes)
            for fn in listeners:
                fn(rec)


def count(name: str, n: int = 1, *, request: Optional[Hashable] = None) -> None:
    """Add ``n`` to counter ``name``, process-wide and for ``request``
    (by default the request of the span open on this thread, if any)."""
    if request is None:
        stack = getattr(_span_stack, "open", None)
        if stack:
            request = stack[-1].request
    with _span_lock:
        _counter_totals[name] = _counter_totals.get(name, 0) + n
        if request is not None:
            counters = _request(request)[1]
            counters[name] = counters.get(name, 0) + n


def add_span_listener(fn: Callable[[SpanRecord], None]) -> None:
    """Call ``fn(record)`` for every span closed from now on, on the
    thread that closed it."""
    with _span_lock:
        _span_listeners.append(fn)


def remove_span_listener(fn: Callable[[SpanRecord], None]) -> None:
    with _span_lock:
        _span_listeners.remove(fn)


def _span_dict(totals: dict) -> dict[str, dict[str, Any]]:
    return {
        name: {"count": c, "seconds": ns / 1e9, "self_seconds": self_ns / 1e9, "nbytes": b}
        for name, (c, ns, self_ns, b) in totals.items()
    }


def span_stats() -> dict[str, Any]:
    """Span and counter totals: ``{"totals": {name: {count, seconds,
    self_seconds, nbytes}}, "counters": {name: n}, "requests":
    [{"request": r, "spans": {name: ...}, "counters": {name: n}}, ...]}``,
    the requests oldest first."""
    with _span_lock:
        return {
            "totals": _span_dict(_span_totals),
            "counters": dict(_counter_totals),
            "requests": [{"request": r, "spans": _span_dict(spans), "counters": dict(counters)}
                         for r, (spans, counters) in _request_totals.items()],
        }


@dataclasses.dataclass(frozen=True)
class TelemetrySnapshot:
    """Point-in-time copy of every introspection surface (safe to
    mutate; the live counters are not aliased)."""

    #: per-scheduler pre-filter events (engaged / accepted / fallback /
    #: bypassed / promoted) — ``repro.core.prefilter.stats()``.
    prefilter: dict[str, dict[str, int]]
    #: EC coefficient-matrix builds and LRU hit rates —
    #: ``repro.kernels.ops.matrix_cache_stats()``.
    matrix_cache: dict[str, Any]
    #: jit compile census per kernel family —
    #: ``repro.core.shapes.compile_cache_stats()``.
    compile_cache: dict[str, Any]
    #: decision counters of the engine passed to :func:`snapshot`
    #: (placements, rejections, constraint swaps, repair gauges), or
    #: ``None`` when no engine was given.
    engine: Optional[dict[str, Any]] = None
    #: persistent XLA compilation-cache state —
    #: ``repro.core.jitcache.status()``.
    jit_cache: Optional[dict[str, Any]] = None
    #: host span and counter totals, process-wide and per request —
    #: :func:`span_stats`.
    spans: Optional[dict[str, Any]] = None

    def as_dict(self) -> dict[str, Any]:
        """Plain-dict view for JSON stamping."""
        return dataclasses.asdict(self)


def snapshot(engine=None) -> TelemetrySnapshot:
    """Copy every introspection surface; pass a
    :class:`~repro.core.engine.PlacementEngine` to include its
    per-instance decision counters."""
    from repro.core import jitcache, prefilter, shapes
    from repro.kernels import ops as kops

    return TelemetrySnapshot(
        prefilter=prefilter.stats(),
        matrix_cache=kops.matrix_cache_stats(),
        compile_cache=shapes.compile_cache_stats(),
        engine=dict(engine.stats) if engine is not None else None,
        jit_cache=jitcache.status(),
        spans=span_stats(),
    )


def reset(
    *,
    prefilter_counters: bool = True,
    matrix_caches: bool = True,
    compile_census: bool = True,
    spans: bool = True,
) -> None:
    """Zero the process-wide counters (benchmark lane isolation).

    Engine counters are per-instance and unaffected — construct a fresh
    engine instead.  Resetting the compile census clears the bucketer's
    issued-shape census, not the jit caches themselves.  Resetting the
    spans clears their totals and the counters' (:func:`count`);
    listeners stay registered.
    """
    from repro.core import prefilter, shapes
    from repro.kernels import ops as kops

    if prefilter_counters:
        prefilter.reset_stats()
    if matrix_caches:
        kops.reset_matrix_caches()
    if compile_census:
        shapes.reset()
    if spans:
        with _span_lock:
            _span_totals.clear()
            _counter_totals.clear()
            _request_totals.clear()
