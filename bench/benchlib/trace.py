"""Reduce a profiler trace to device busy time, op totals and idle gaps.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes with nothing but
``jax.profiler.ProfileData``. Device planes are those named
``/device:<KIND>:<n>``; their ``XLA Ops`` line holds one event per device
operation. Host planes carry the benchmark's own spans (``bench.*``
``TraceAnnotation`` blocks) and the runtime's host events, all on the same
clock as the device events.
"""
from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

Interval = Tuple[float, float]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint union of half-open intervals."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of ``[lo, hi)`` that ``busy`` (a disjoint union) leaves."""
    out, cur = [], lo
    for a, b in busy:
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if cur < hi:
        out.append((cur, hi))
    return out


@dataclass
class Event:
    name: str
    start: float                # ns
    end: float                  # ns
    thread: str = ""


@dataclass
class Reduction:
    """Device activity inside the benchmark's window span."""
    window_s: float
    busy_s: float               # union of op intervals, mean over devices
    n_devices: int
    op_seconds: Dict[str, float]
    idle_gaps: List[Tuple[str, float]]     # longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


@dataclass
class Trace:
    device_ops: Dict[str, List[Event]] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)

    def host_arrays(self) -> Tuple[List[str], np.ndarray, np.ndarray]:
        names = [e.name for e in self.host]
        return (names, np.asarray([e.start for e in self.host], np.float64),
                np.asarray([e.end for e in self.host], np.float64))


def load(path: str) -> Trace:
    """Device ops per device plane and every host event of one xplane
    file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = Trace()
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            evs, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend(Event(e.name, e.start_ns,
                                     e.start_ns + e.duration_ns)
                               for e in line.events)
                elif line.name == MODULES_LINE:
                    mods.extend(Event(e.name, e.start_ns,
                                      e.start_ns + e.duration_ns)
                                for e in line.events)
            if evs:
                out.device_ops[plane.name] = name_ops(evs, mods)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out.host.extend(Event(e.name, e.start_ns,
                                      e.start_ns + e.duration_ns, line.name)
                                for e in line.events if e.duration_ns > 0)
    return out


def name_ops(ops: List[Event], modules: List[Event]) -> List[Event]:
    """Ops renamed ``<program>:<instruction> = <result type>``: the
    enclosing program (``XLA Modules`` event, its id dropped) and the
    HLO text up to its first layout brace."""
    mods = sorted(modules, key=lambda m: m.start)
    starts = [m.start for m in mods]
    out = []
    for e in ops:
        i = bisect.bisect_right(starts, e.start) - 1
        prog = mods[i].name.split("(")[0] if i >= 0 and \
            mods[i].end >= e.start else "?"
        out.append(Event(f"{prog}:{e.name.split('{')[0].strip()}",
                         e.start, e.end))
    return out


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def gap_label(gap: Interval, names: Sequence[str], start: np.ndarray,
              end: np.ndarray) -> str:
    """What the host was doing in ``gap``: the benchmark spans that overlap
    it (``wait`` only where nothing else does), and the runtime host event
    that overlaps it most."""
    a, b = gap
    ov = np.minimum(b, end) - np.maximum(a, start)
    spans, best, best_ov = set(), "", 0.0
    for i in np.flatnonzero(ov > 0):
        name = names[i]
        if name.startswith(SPAN_PREFIX):
            if name != WINDOW_SPAN:
                spans.add(name[len(SPAN_PREFIX):])
        elif ov[i] > best_ov:
            best, best_ov = name, float(ov[i])
    if len(spans) > 1:
        spans.discard("wait")
    label = "+".join(sorted(spans)) or "none"
    return f"{label}:{best}" if best else label


def reduce(trace: Trace, top: int = 10) -> Reduction:
    """Busy time, op totals and the ``top`` longest idle gaps inside the
    ``bench.window`` span (the first one in the trace)."""
    windows = [e for e in trace.host if e.name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
    w = min(windows, key=lambda e: e.start)
    lo, hi = w.start, w.end
    if not trace.device_ops:
        raise ValueError("the trace has no device operations")
    busy_total = 0.0
    ops: Dict[str, float] = {}
    first_busy: Optional[List[Interval]] = None
    for plane in sorted(trace.device_ops):
        evs = trace.device_ops[plane]
        busy = union(clip(((e.start, e.end) for e in evs), lo, hi))
        busy_total += sum(b - a for a, b in busy)
        for e in evs:
            d = min(e.end, hi) - max(e.start, lo)
            if d > 0:
                ops[e.name] = ops.get(e.name, 0.0) + d * 1e-9
        if first_busy is None:
            first_busy = busy
    n_dev = len(trace.device_ops)
    idle = sorted(gaps(first_busy, lo, hi), key=lambda g: g[0] - g[1])[:top]
    names, start, end = trace.host_arrays()
    labelled = [(gap_label(g, names, start, end), (g[1] - g[0]) * 1e-9)
                for g in idle]
    return Reduction(window_s=(hi - lo) * 1e-9,
                     busy_s=busy_total * 1e-9 / n_dev, n_devices=n_dev,
                     op_seconds=ops, idle_gaps=labelled)
