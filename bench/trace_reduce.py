"""Reduction of a JAX profiler trace to the numbers the benchmark reports.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes with
``jax.profiler.ProfileData`` (nothing but JAX).  Device planes are those
named ``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per
device operation.  Host spans are the benchmark's own
``TraceAnnotation``s, named ``bench.<what>``; the span named
``bench.trace_window`` bounds the traced window.

* busy: the union of the operation intervals inside the window, per
  device, averaged over the devices;
* op time: the summed durations of each operation, by its HLO
  instruction name, over devices; an operation that encloses others on
  the same line (a ``while`` loop and its body) is left out of the sums,
  so no time counts twice;
* idle gaps: the stretches of the window in which device 0 runs nothing,
  each named by the host span that overlaps it most.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.trace_window"
HOST_PREFIX = "bench."
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                          # averaged over devices
    n_devices: int
    op_s: Dict[str, float]                 # op name -> summed seconds
    op_detail: Dict[str, str]              # op name -> its string stats
    idle_gaps: List[Tuple[str, float]]     # (host span, s), longest first

    def top_ops(self, n: int = 10) -> List[list]:
        return [[k, v] for k, v in
                sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]]

    def matching_s(self, needle: str) -> float:
        """Summed seconds of the ops whose name or stats hold ``needle``."""
        return sum(s for k, s in self.op_s.items()
                   if needle in k or needle in self.op_detail.get(k, ""))


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def gaps_of(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """Complement of sorted disjoint ``busy`` within [lo, hi]."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def label_gap(gap: Interval, spans: List[Tuple[str, float, float]]) -> str:
    best, best_ov = "no benchmark span", 0.0
    for name, a, b in spans:
        ov = min(b, gap[1]) - max(a, gap[0])
        if ov > best_ov:
            best, best_ov = name, ov
    return best


def op_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def leaves(events: List[Tuple[float, float, object]]):
    """The events that enclose no other event of their line."""
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    parent = [False] * len(events)
    stack: List[int] = []
    for i, (a, b, _) in enumerate(events):
        while stack and events[stack[-1]][1] <= a:
            stack.pop()
        if stack:
            parent[stack[-1]] = True
        stack.append(i)
    return [e for e, p in zip(events, parent) if not p]


def _stats_text(ev) -> str:
    try:
        return " ".join(str(v) for _, v in ev.stats if isinstance(v, str))
    except (TypeError, ValueError):
        return ""


def find_xplane(directory: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def reduce(path: str, n_gaps: int = 10) -> Optional[TraceSummary]:
    """The summary of one trace; None when it holds no device plane or no
    window span."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans: List[Tuple[str, float, float]] = []
    devices: List[list] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices.append([line for line in plane.lines
                            if line.name == OPS_LINE])
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    windows = [(a, b) for n, a, b in spans if n == WINDOW_SPAN]
    if not devices or not windows:
        return None
    lo, hi = windows[0]
    spans = [s for s in spans if s[0] != WINDOW_SPAN]
    op_s: Dict[str, float] = {}
    op_detail: Dict[str, str] = {}
    busy_total = 0.0
    busy0: List[Interval] = []
    for d, lines in enumerate(devices):
        ivs = []
        for line in lines:
            evs = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev)
                   for ev in line.events]
            evs = [e for e in evs if e[1] > lo and e[0] < hi]
            ivs.extend((a, b) for a, b, _ in evs)
            for a, b, ev in leaves(evs):
                name = op_name(ev.name)
                op_s[name] = op_s.get(name, 0.0) + \
                    (min(b, hi) - max(a, lo)) * 1e-9
                if name not in op_detail:
                    op_detail[name] = _stats_text(ev)
        merged = union(clip(ivs, lo, hi))
        busy_total += sum(b - a for a, b in merged)
        if d == 0:
            busy0 = merged
    gaps = sorted(gaps_of(busy0, lo, hi), key=lambda g: g[0] - g[1])
    return TraceSummary(
        window_s=(hi - lo) * 1e-9, busy_s=busy_total / len(devices) * 1e-9,
        n_devices=len(devices), op_s=op_s, op_detail=op_detail,
        idle_gaps=[(label_gap(g, spans), (g[1] - g[0]) * 1e-9)
                   for g in gaps[:n_gaps]])
