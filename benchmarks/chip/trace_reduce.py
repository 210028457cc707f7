"""Reduce a profiler trace (``.xplane.pb``) to device time.

* The window is the host annotation ``bench.window`` that the harness
  opens around its measured window.
* A device is a plane named ``/device:...`` with an ``XLA Ops`` line; its
  operations are the events of that line; busy time is the union of their
  intervals inside the window, averaged over the devices.
* A jitted function's device time is the summed duration of its events
  on the ``XLA Modules`` line (``jit_field_encode(...)``), or, where a
  trace has no such line, of the operations whose ``hlo_module`` is it.
* An idle gap is a stretch of the window in which no operation ran on a
  device.  It is named by the innermost of the harness's own host
  annotations (``bench.*``, ``codec.*``) that covers at least half of it;
  failing that, by the host event that covers most of it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]
OWN = ("bench.", "codec.")


def find_xplane(directory: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def _merge(intervals: List[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


@dataclasses.dataclass
class Device:
    ops: List[Tuple[str, float, float, str]]     # name, start, end, module
    modules: List[Tuple[str, float, float]]      # name, start, end


@dataclasses.dataclass
class Trace:
    window: Interval                              # ns
    devices: List[Device]
    host: List[Tuple[str, float, float]]          # name, start, end

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def _busy(self, dev: Device) -> List[Interval]:
        return _merge(_clip([(s, e) for _n, s, e, _m in dev.ops],
                            *self.window))

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(sum(e - s for s, e in self._busy(d))
                   for d in self.devices) / len(self.devices) / 1e9

    def module_seconds(self, prefix: str) -> float:
        """Device seconds of the jitted functions named ``prefix`` inside
        the window, summed over the devices."""
        total = 0.0
        for d in self.devices:
            if d.modules:
                spans = [(s, e) for n, s, e in d.modules
                         if n.startswith(prefix)]
            else:
                spans = [(s, e) for _n, s, e, m in d.ops
                         if m.startswith(prefix)]
            total += sum(e - s for s, e in _clip(spans, *self.window))
        return total / 1e9

    def top_ops(self, n: int = 10) -> List[list]:
        """The ``n`` device operations that took most time in the window."""
        acc: Dict[str, float] = {}
        for d in self.devices:
            for name, s, e in ((o[0], o[1], o[2]) for o in d.ops):
                for cs, ce in _clip([(s, e)], *self.window):
                    acc[name] = acc.get(name, 0.0) + (ce - cs) / 1e9
        return [[k[:160], v] for k, v in
                sorted(acc.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> List[list]:
        """The ``n`` longest idle gaps of the first device, each named by
        the host annotation that covers most of it."""
        if not self.devices:
            return []
        lo, hi = self.window
        gaps, t = [], lo
        for s, e in self._busy(self.devices[0]):
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if hi > t:
            gaps.append((t, hi))
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
        return [[self._name(s, e), (e - s) / 1e9] for s, e in gaps]

    def _name(self, s: float, e: float) -> str:
        best, best_key = "none", None
        for name, hs, he in self.host:
            cover = min(e, he) - max(s, hs)
            if cover <= 0 or name == "bench.window":
                continue
            own = name.startswith(OWN)
            half = own and 2 * cover >= e - s
            key = (half, own, -(he - hs) if half else cover)
            if best_key is None or key > best_key:
                best, best_key = name, key
        return best


def reduce(path: str) -> Trace:
    """Read one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: List[Device] = []
    host: List[Tuple[str, float, float]] = []
    window: Optional[Interval] = None
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        stats = dict(ev.stats)
                        ops.append((ev.name, ev.start_ns,
                                    ev.start_ns + ev.duration_ns,
                                    str(stats.get("hlo_module", ""))))
                elif line.name == "XLA Modules":
                    modules.extend((ev.name, ev.start_ns,
                                    ev.start_ns + ev.duration_ns)
                                   for ev in line.events)
            if ops:
                devices.append(Device(ops, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    end = ev.start_ns + ev.duration_ns
                    if ev.name == "bench.window":
                        window = (ev.start_ns, end)
                    host.append((ev.name, ev.start_ns, end))
    if window is None:
        raise ValueError(f"{path}: no bench.window annotation")
    return Trace(window, devices, host)
