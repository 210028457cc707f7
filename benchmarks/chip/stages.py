"""Sums over the program's own spans and counters for the per-layer metric
readers: the wall time, and the threads' CPU time, of the spans of some
names, over the work done in the window; a jitted function's device time
over the values the program counted through it.  Each is ``None`` where
the window holds no span of those names, or none that measured its CPU
time, or no such count, as in a program that does not open, measure or
count them."""
from __future__ import annotations

from typing import Optional


def span_ms(ctx, *names: str) -> Optional[float]:
    """Milliseconds of every span named in ``names``."""
    spans = [s for s in ctx.spans if s.name in names]
    if not spans:
        return None
    return sum(s.duration_us for s in spans) / 1e3


def span_cpu_ms(ctx, *names: str) -> Optional[float]:
    """Milliseconds of the threads' CPU time (the ``cpu_ns`` slot) in every
    span named in ``names``."""
    cpu = [getattr(s, "cpu_ns", None) for s in ctx.spans if s.name in names]
    cpu = [c for c in cpu if c is not None]
    return sum(cpu) / 1e6 if cpu else None


def per_gb(ctx, value: Optional[float], kind: str) -> Optional[float]:
    """``value`` per GB of the work of ``kind`` done in the window."""
    gb = sum(w.nbytes for w in ctx.done(kind)) / 1e9
    if value is None or gb <= 0:
        return None
    return value / gb


def per_done(ctx, value: Optional[float], kind: str) -> Optional[float]:
    """``value`` per piece of work of ``kind`` done in the window."""
    n = len(ctx.done(kind))
    if value is None or n == 0:
        return None
    return value / n


def device_ns_per_value(ctx, module: str, counter: str) -> Optional[float]:
    """Device nanoseconds of the jitted functions named ``module`` per
    value the program counted in ``counter`` over the window."""
    n = ctx.counters.get(counter, 0)
    if ctx.trace is None or n <= 0:
        return None
    t = ctx.trace.module_seconds(module)
    return 1e9 * t / n if t > 0 else None
