"""Lightweight structured tracing: spans, context propagation, exporters.

A :class:`Span` is one timed phase of the I/O stack — ``plan.resolve``,
``io.fetch``, ``codec.decode``, ``fdb.flush`` — with monotonic-clock
timestamps (``time.perf_counter_ns``), free-form attributes, and a parent
link.  The active span rides a :mod:`contextvars` ContextVar, so nesting
``with tracer.span(...)`` blocks builds the parent/child tree implicitly,
and because :class:`~repro.tensorstore.executor.ChunkExecutor` submits
work through ``contextvars.copy_context()``, spans opened inside worker
threads keep their caller's span as parent — a read plan's ``io.fetch``
spans land under its ``plan.execute`` even though they run on pool
threads.

Design points:

* **Near-zero cost when disabled.**  ``Tracer.span()`` on a disabled
  tracer returns a shared no-op context manager — one attribute check,
  no allocation, no clock read.  The instrumented hot paths stay within
  noise of the uninstrumented build.
* **Bounded buffer.**  Finished spans go into a ``TraceBuffer`` (a
  capacity-capped deque).  ``mark()``/``spans(since=...)`` give windowed
  access — the bench harness marks before each timed phase and pulls
  only that phase's spans.  Overflow evicts oldest and is counted, never
  raised.
* **Exporters, not a pipeline.**  ``chrome_trace()`` emits Chrome
  ``trace_event`` JSON (open in https://ui.perfetto.dev), ``rollup()`` a
  plain-text per-name table, ``phase_totals()`` the queue/io/decode/
  encode split the bench columns report.  All are pull-based; nothing
  runs unless asked.

* **Per-span CPU time.**  An enabled span named in
  :data:`CPU_TIMED_SPANS` also records the thread's CPU time between its
  entry and exit (``cpu_ns``), so a slow span can be told apart as more
  work or waiting.  Two ``thread_time_ns`` reads are a system call each,
  so the other spans take none.
* **A mirror onto another clock.**  :func:`set_span_mirror` installs a
  factory of context managers that every span an enabled tracer opens
  with ``with`` also enters — :mod:`repro.kernels.ops` installs
  ``jax.profiler.TraceAnnotation``, so the spans land in the profiler's
  trace beside the device's operations.

This module is stdlib-only and imports nothing from ``repro`` except its
sibling :mod:`.metrics`, so any layer (backends, executor, kernels) can
import it without cycles.
"""
from __future__ import annotations

import itertools
import json
import threading
import time
from collections import deque
from contextvars import ContextVar
from typing import Any, Callable, ContextManager, Dict, List, Optional

from .metrics import MetricsRegistry

#: the active span for the current logical context (thread or copied
#: context inside an executor worker); None when not inside any span
_SPAN_VAR: ContextVar[Optional["Span"]] = ContextVar("repro_obs_span",
                                                     default=None)

#: span names that count toward each wall-time phase in
#: :meth:`Tracer.phase_totals`.  Exact names, not prefixes: nested spans
#: (``plan.execute`` around ``io.fetch``) must not double-count.
PHASE_SPANS: Dict[str, frozenset] = {
    "queue": frozenset({"executor.queue"}),
    "io": frozenset({"io.fetch", "io.archive"}),
    "decode": frozenset({"codec.decode"}),
    "encode": frozenset({"codec.encode"}),
}

DEFAULT_CAPACITY = 1 << 16

#: spans that record the thread's CPU time (``Span.cpu_ns``) when enabled:
#: the write path's encode and per-object archive
CPU_TIMED_SPANS = frozenset({"codec.encode", "io.archive"})

#: factory of a context manager entered around every span an enabled
#: tracer opens with ``with`` (see :func:`set_span_mirror`)
_MIRROR: Optional[Callable[[str], ContextManager]] = None


def set_span_mirror(
        factory: Optional[Callable[[str], ContextManager]]) -> None:
    """Mirror spans onto another timeline: while ``factory`` is installed,
    each span an *enabled* tracer opens with ``with`` also enters
    ``factory(name)`` and exits it when the span closes (``None``, the
    default, mirrors nothing).  A disabled tracer never touches it.

    Spans recorded after the fact (:meth:`Tracer.record_complete`, such as
    ``executor.queue``) are not mirrored: their interval is over before
    they are known."""
    global _MIRROR
    _MIRROR = factory


class Span:
    """One finished (or in-flight) timed phase.

    ``span_id``/``parent_id`` are tracer-local integers; ``parent_id`` is
    None for roots.  ``attrs`` is mutable while the span is open — callers
    set e.g. ``nbytes`` once known (``sp.attrs["nbytes"] = n``).
    ``cpu_ns``, the thread's CPU time over the span, is set when a span
    named in :data:`CPU_TIMED_SPANS` closes, and stays None on every
    other span.
    """

    __slots__ = ("tracer", "name", "span_id", "parent_id", "thread_id",
                 "t0_ns", "t1_ns", "attrs", "cpu_ns")

    def __init__(self, tracer: "Tracer", name: str, span_id: int,
                 parent_id: Optional[int], thread_id: int, t0_ns: int,
                 attrs: Dict[str, Any]):
        self.tracer = tracer
        self.name = name
        self.span_id = span_id
        self.parent_id = parent_id
        self.thread_id = thread_id
        self.t0_ns = t0_ns
        self.t1_ns: Optional[int] = None
        self.attrs = attrs
        self.cpu_ns: Optional[int] = None

    @property
    def duration_us(self) -> float:
        end = self.t1_ns if self.t1_ns is not None else time.perf_counter_ns()
        return (end - self.t0_ns) / 1_000.0

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"Span({self.name!r}, id={self.span_id}, "
                f"parent={self.parent_id}, {self.duration_us:.1f}us)")


class _NoopSpan:
    """Shared do-nothing context manager — the disabled-tracing fast path.

    ``__enter__`` returns None, so instrumentation that annotates the
    span (``if sp is not None: sp.attrs[...] = ...``) skips cleanly.
    """

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, exc_type, exc, tb):
        return False


_NOOP = _NoopSpan()


class _SpanCM:
    """Context manager that opens a real span on a specific tracer."""

    __slots__ = ("_tracer", "_name", "_attrs", "_span", "_token",
                 "_mirror", "_cpu0")

    def __init__(self, tracer: "Tracer", name: str, attrs: Dict[str, Any]):
        self._tracer = tracer
        self._name = name
        self._attrs = attrs

    def __enter__(self) -> Span:
        tr = self._tracer
        parent = _SPAN_VAR.get()
        # a parent from a *different* tracer (two FDB clients with private
        # buffers in one context) would dangle — treat as root instead
        parent_id = (parent.span_id
                     if parent is not None and parent.tracer is tr else None)
        span = Span(tr, self._name, next(tr._ids), parent_id,
                    threading.get_ident(), time.perf_counter_ns(),
                    self._attrs)
        factory = _MIRROR
        self._mirror = factory(self._name) if factory is not None else None
        if self._mirror is not None:
            self._mirror.__enter__()
        self._span = span
        self._token = _SPAN_VAR.set(span)
        self._cpu0 = (time.thread_time_ns()
                      if self._name in CPU_TIMED_SPANS else None)
        return span

    def __exit__(self, exc_type, exc, tb):
        span = self._span
        span.t1_ns = time.perf_counter_ns()
        if self._cpu0 is not None:
            span.cpu_ns = time.thread_time_ns() - self._cpu0
        if self._mirror is not None:
            self._mirror.__exit__(exc_type, exc, tb)
        if exc_type is not None:
            span.attrs["error"] = exc_type.__name__
        _SPAN_VAR.reset(self._token)
        self._tracer._record(span)
        return False


class TraceBuffer:
    """Bounded in-memory store of finished spans.

    Append-only from the tracer's point of view; eviction (oldest first)
    happens silently at ``capacity`` and is reported via ``dropped``.
    ``total`` counts every span ever recorded, so ``mark()``/``since``
    windows remain valid across evictions.
    """

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self.capacity = capacity
        self._buf: deque = deque(maxlen=capacity)
        self._total = 0
        self._lock = threading.Lock()

    def append(self, span: Span) -> None:
        with self._lock:
            self._buf.append(span)
            self._total += 1

    @property
    def total(self) -> int:
        return self._total

    @property
    def dropped(self) -> int:
        return self._total - len(self._buf)

    def window(self, since: int = 0) -> List[Span]:
        """Spans recorded at or after sequence number ``since`` (from
        :meth:`Tracer.mark`), oldest first."""
        with self._lock:
            buf = list(self._buf)
            total = self._total
        first_kept = total - len(buf)  # seq number of buf[0]
        skip = max(0, since - first_kept)
        return buf[skip:]

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()
            self._total = 0


class Tracer:
    """A trace buffer + metrics registry + span factory.

    One per FDB client by default (clients share :data:`GLOBAL_TRACER`
    unless given their own), mirroring how ``GLOBAL_METER`` works for
    byte/op accounting.  Disabled by default; ``enable()`` or construct
    with ``enabled=True``.
    """

    def __init__(self, enabled: bool = False,
                 capacity: int = DEFAULT_CAPACITY,
                 metrics: Optional[MetricsRegistry] = None):
        self.enabled = enabled
        self.buffer = TraceBuffer(capacity)
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self._ids = itertools.count(1)

    # -- control ------------------------------------------------------------
    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def clear(self) -> None:
        self.buffer.clear()
        self.metrics.clear()

    # -- recording ----------------------------------------------------------
    def span(self, name: str, **attrs: Any):
        """Open a span: ``with tracer.span("io.fetch", backend="daos") as sp``.

        Returns the shared no-op when disabled (``sp`` is then None).
        """
        if not self.enabled:
            return _NOOP
        return _SpanCM(self, name, attrs)

    def record_complete(self, name: str, t0_ns: int, t1_ns: int,
                        parent: Optional[Span] = None,
                        **attrs: Any) -> Optional[Span]:
        """Record an already-measured interval (e.g. executor queue wait,
        where the start is on the submitting thread and the end on the
        worker).  ``parent`` is explicit because no ``with`` block wrapped
        the interval."""
        if not self.enabled:
            return None
        parent_id = (parent.span_id
                     if parent is not None and parent.tracer is self else None)
        span = Span(self, name, next(self._ids), parent_id,
                    threading.get_ident(), t0_ns, attrs)
        span.t1_ns = t1_ns
        self._record(span)
        return span

    def _record(self, span: Span) -> None:
        self.buffer.append(span)
        # backend store ops double as latency histograms — one place,
        # every backend, no per-backend metric plumbing
        if span.name.startswith("store."):
            self.metrics.histogram(span.name + "_us").observe(
                span.duration_us)

    # -- windowed access ----------------------------------------------------
    def mark(self) -> int:
        """Sequence number for ``since=`` windows: record, do work, then
        ``spans(since=mark)`` / ``phase_totals(since=mark)``."""
        return self.buffer.total

    def spans(self, since: int = 0) -> List[Span]:
        return self.buffer.window(since)

    @property
    def dropped(self) -> int:
        return self.buffer.dropped

    # -- exporters ----------------------------------------------------------
    def chrome_events(self, since: int = 0, pid: int = 0) -> List[Dict]:
        """Chrome ``trace_event`` "X" (complete) events for the window.

        Timestamps are perf-counter microseconds — consistent within a
        process, which is all Perfetto needs to lay out the timeline.
        ``args`` holds the attrs and, where measured, ``cpu_ns``.
        """
        events = []
        for s in self.spans(since):
            args = {k: _jsonable(v) for k, v in s.attrs.items()}
            if s.cpu_ns is not None:
                args["cpu_ns"] = s.cpu_ns
            events.append({
                "name": s.name, "ph": "X", "pid": pid, "tid": s.thread_id,
                "ts": s.t0_ns / 1_000.0,
                "dur": round(s.duration_us, 3),
                "args": args,
            })
        return events

    def chrome_trace(self, since: int = 0, pid: int = 0,
                     process_name: str = "repro") -> Dict[str, Any]:
        """A complete, Perfetto-loadable trace document."""
        meta = [{"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
                 "args": {"name": process_name}}]
        return {"traceEvents": meta + self.chrome_events(since, pid),
                "displayTimeUnit": "ms"}

    def write_chrome_trace(self, path: str, since: int = 0,
                           process_name: str = "repro") -> None:
        with open(path, "w") as fh:
            json.dump(self.chrome_trace(since, process_name=process_name), fh)

    def phase_totals(self, since: int = 0) -> Dict[str, float]:
        """Summed span time (µs) per wall-time phase: queue / io / decode /
        encode — the ``t_*`` bench columns.  Counts only the leaf span
        names in :data:`PHASE_SPANS`, so wrapping spans never double-count;
        concurrent spans sum, so totals can legitimately exceed wall time
        when the executor overlaps I/O."""
        totals = {phase: 0.0 for phase in PHASE_SPANS}
        for s in self.spans(since):
            for phase, names in PHASE_SPANS.items():
                if s.name in names:
                    totals[phase] += s.duration_us
        return {k: round(v, 3) for k, v in totals.items()}

    def rollup(self, since: int = 0) -> str:
        """Plain-text per-name table: count, total/mean/max µs."""
        agg: Dict[str, List[float]] = {}
        for s in self.spans(since):
            agg.setdefault(s.name, []).append(s.duration_us)
        if not agg:
            return "(no spans recorded)"
        name_w = max(len(n) for n in agg)
        lines = [f"{'span':<{name_w}}  {'count':>7} {'total_us':>12} "
                 f"{'mean_us':>10} {'max_us':>10}"]
        for name in sorted(agg):
            ds = agg[name]
            lines.append(f"{name:<{name_w}}  {len(ds):>7} {sum(ds):>12.1f} "
                         f"{sum(ds) / len(ds):>10.1f} {max(ds):>10.1f}")
        if self.dropped:
            lines.append(f"[trace buffer overflow: {self.dropped} oldest "
                         f"spans evicted]")
        return "\n".join(lines)


def _jsonable(v: Any) -> Any:
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return str(v)


# -- ambient helpers --------------------------------------------------------

def current_span() -> Optional[Span]:
    """The active span in this context, or None."""
    return _SPAN_VAR.get()


def span(name: str, **attrs: Any):
    """Ambient span: attach to whatever traced operation is in flight.

    Used by layers with no tracer handle of their own (the simulated
    backends, the executor) — if the caller is inside a traced span, the
    new span joins that tracer; otherwise this is the no-op fast path.
    """
    s = _SPAN_VAR.get()
    if s is None or not s.tracer.enabled:
        return _NOOP
    return _SpanCM(s.tracer, name, attrs)


def count(name: str, n: int = 1) -> None:
    """Ambient counter, the twin of :func:`span`: add ``n`` to the counter
    ``name`` of whatever traced operation is in flight (a layer with no
    tracer handle, such as the codec, counts into its caller's tracer);
    outside one, a no-op."""
    s = _SPAN_VAR.get()
    if s is not None and s.tracer.enabled:
        s.tracer.metrics.counter(name).inc(n)


#: process-wide default tracer, disabled out of the box — mirrors
#: ``GLOBAL_METER``.  ``benchmarks.run --trace`` enables it; FDB clients
#: use it unless constructed with a private tracer.
GLOBAL_TRACER = Tracer(enabled=False)


__all__ = ["Span", "Tracer", "TraceBuffer", "GLOBAL_TRACER", "PHASE_SPANS",
           "CPU_TIMED_SPANS", "DEFAULT_CAPACITY", "span", "count", "current_span",
           "set_span_mirror"]
