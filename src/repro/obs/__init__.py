"""Observability layer: structured tracing + metrics for the I/O stack.

Stdlib-only on purpose — every layer of the repo (backends, executor,
plans, facade) can import this package without creating a cycle or a
dependency.  See ``docs/observability.md`` for the span taxonomy and the
metric name registry.
"""
from .locks import NamedLock, held_locks, set_lock_observer
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      DEFAULT_LATENCY_BUCKETS_US)
from .trace import (GLOBAL_TRACER, PHASE_SPANS, Span, TraceBuffer, Tracer,
                    count, current_span, set_span_mirror, span)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "DEFAULT_LATENCY_BUCKETS_US",
    "GLOBAL_TRACER", "PHASE_SPANS", "Span", "TraceBuffer", "Tracer",
    "count", "current_span", "set_span_mirror", "span",
    "NamedLock", "held_locks", "set_lock_observer",
]
