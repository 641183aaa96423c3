"""Reduction of a JAX profiler trace to the benchmark's device numbers.

The profiler writes one ``.xplane.pb`` per traced window. Its device planes
(``/device:TPU:<n>``) hold two lines this file reads: ``XLA Modules`` (one
event per program run, named ``jit_<function>(<fingerprint>)``) and ``XLA
Ops`` (one event per operation inside it). Host planes hold the
benchmark's own ``jax.profiler.TraceAnnotation`` events, on the same clock.

* busy time: the union of the device's op intervals inside the window;
* device time of a program: the summed durations of its module events;
* ``breakdown``: the device operations that took most time, and the idle
  gaps of the device, each named by the innermost host span (the program's
  ``repro.obs`` spans, moved onto the trace's clock) open at its midpoint.
"""

from __future__ import annotations

import glob
import os
import re

__all__ = ["Trace", "union", "load"]

WINDOW = "bench.window"   # TraceAnnotation around the measured window
UNIT = "bench.unit"       # TraceAnnotation around each unit of work


def _merged(intervals):
    """Sorted, disjoint [start, end] runs covering the intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def union(intervals) -> float:
    """Length covered by a set of (start, end) intervals."""
    return sum(b - a for a, b in _merged(intervals))


def program_name(module: str) -> str:
    """``jit_views(7381...)`` -> ``views``."""
    name = re.sub(r"\(\d+\)$", "", module)
    return name[4:] if name.startswith("jit_") else name


def op_name(op: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return op.split(" = ", 1)[0].lstrip("%")


class Trace:
    """Events of one traced window, in seconds on the trace's clock.

    ``modules`` / ``ops``: per device, lists of (name, start, end) clipped
    to the window; ``units``: start and end of each unit annotation;
    ``window``: (start, end) of the window annotation.
    """

    def __init__(self, planes: dict):
        host = planes.get("host", [])
        wins = [(s, e) for n, s, e in host if n == WINDOW]
        if len(wins) != 1:
            raise ValueError(f"expected one {WINDOW} annotation, "
                             f"found {len(wins)}")
        self.window = wins[0]
        lo, hi = self.window
        self.units = sorted((s, e) for n, s, e in host if n == UNIT)
        clip = lambda evs: [(n, max(s, lo), min(e, hi)) for n, s, e in evs
                            if e > lo and s < hi]
        # A trace taken off the chip has no device plane: nothing is busy.
        self.modules = [clip(d["modules"]) for d in planes["devices"]] or [[]]
        self.ops = [clip(d["ops"]) for d in planes["devices"]] or [[]]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        return sum(union((s, e) for _, s, e in d) for d in self.ops) \
            / len(self.ops)

    def idle_pct(self):
        """Percent of the window in which no operation ran on the device."""
        if self.window_s <= 0.0:
            return None
        return 100.0 * (1.0 - self.busy_s() / self.window_s)

    def program_s(self, names) -> float:
        """Device seconds of the named programs, summed over the devices."""
        names = set(names)
        return sum(e - s for d in self.modules for n, s, e in d
                   if program_name(n) in names)

    def top_ops(self, n: int = 10):
        """[[op, seconds]] of the operations that took most device time."""
        agg: dict = {}
        for d in self.ops:
            for name, s, e in d:
                k = op_name(name)
                agg[k] = agg.get(k, 0.0) + (e - s)
        return [[k, v] for k, v in sorted(agg.items(), key=lambda kv: -kv[1])
                [:n]]

    def idle_gaps(self, spans, n: int = 10):
        """[[host span, idle seconds]]: the device's idle time in the window
        (device 0), summed by the innermost host span open at each gap's
        midpoint; ``spans`` are (name, start, end) on the trace's clock."""
        lo, hi = self.window
        busy = _merged((s, e) for _, s, e in self.ops[0])
        gaps, t = [], lo
        for a, b in busy:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if hi > t:
            gaps.append((t, hi))
        agg: dict = {}
        for a, b in gaps:
            mid = 0.5 * (a + b)
            open_ = [(s, -e, name) for name, s, e in spans if s <= mid < e]
            name = max(open_)[2] if open_ else "outside any span"
            agg[name] = agg.get(name, 0.0) + (b - a)
        return [[k, v] for k, v in sorted(agg.items(), key=lambda kv: -kv[1])
                [:n]]


def read_planes(path: str) -> dict:
    """The events this file reduces, from one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") or plane.name.startswith(
                "/device:GPU:"):
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(
                    line.name)
                if key:
                    dev[key] = [(e.name, e.start_ns * 1e-9,
                                 (e.start_ns + e.duration_ns) * 1e-9)
                                for e in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                            for e in line.events
                            if e.name in (WINDOW, UNIT))
    return {"host": host, "devices": devices}


def load(log_dir: str) -> Trace:
    """The newest trace the profiler wrote under ``log_dir``."""
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return Trace(read_planes(paths[-1]))
