"""What a per-layer metric's reader is given: the traced window's device
trace, the program's spans and counters, the harness's own counts, and the
chip's peaks.  Each ``metrics/<name>.py`` defines ``read(ctx)``, which
returns the metric's value or ``None`` where it finds nothing to read.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

from .load import Work
from .trace_reduce import Trace


@dataclasses.dataclass
class Context:
    trace: Optional[Trace]
    #: the program's spans (``repro.obs``) recorded in the window
    spans: list
    #: the program's counters, their growth over the window
    counters: Dict[str, int]
    #: elements and roofline bytes through the codec's batch entry points
    codec: Dict[str, int]
    #: every piece of work the clients issued in the window
    work: List[Work]
    window_s: float
    peaks: Dict[str, float]

    def done(self, kind: str) -> List[Work]:
        return [w for w in self.work if w.kind == kind and w.error is None]

    def span_ms(self, name: str) -> float:
        return sum(s.duration_us for s in self.spans if s.name == name) / 1e3

    def roofline(self, module: str, hbm_bytes_key: str) -> Optional[float]:
        """Percent of the chip's HBM roofline that jitted function
        ``module`` reached on the bytes its work needs."""
        if self.trace is None or "hbm_bytes_per_s" not in self.peaks:
            return None
        t = self.trace.module_seconds(module)
        nbytes = self.codec.get(hbm_bytes_key, 0)
        if t <= 0 or nbytes <= 0:
            return None
        return 100.0 * nbytes / (t * self.peaks["hbm_bytes_per_s"])

    def idle_share(self) -> Optional[float]:
        """Percent of the traced window in which no operation ran on the
        device."""
        if self.trace is None or not self.trace.devices \
                or self.trace.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.trace.busy_s / self.trace.window_s)

    def hit_rate(self) -> Optional[float]:
        hits = self.counters.get("cache.hits", 0)
        misses = self.counters.get("cache.misses", 0)
        if hits + misses == 0:
            return None
        return 100.0 * hits / (hits + misses)

    def span_ms_per_gb(self, span: str, kind: str) -> Optional[float]:
        gb = sum(w.nbytes for w in self.done(kind)) / 1e9
        if gb <= 0 or not self.spans:
            return None
        return self.span_ms(span) / gb
