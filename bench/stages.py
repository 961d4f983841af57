"""Reduction of a profiler trace to the program's named device stages and
host phases.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
JAX and the standard library, in two parts:

* a small reader of XSpace's protobuf wire format for the device planes:
  each ``XLA Ops`` and ``XLA Modules`` event with its event metadata's
  ``tf_op`` stat (``<op_name>:<op_type>``, the op's JAX name stack, which
  holds the ``jax.named_scope`` stages of ``transformer.decode_step`` and
  ``Engine._step_fn``).  ``jax.profiler.ProfileData`` does not expose
  event metadata, so it cannot see the stages;
* ``jax.profiler.ProfileData`` for the host spans: the engine's
  ``engine.*`` phases and the benchmark's ``bench.*`` spans.

The traced stretch is the benchmark's ``bench.trace_window`` span, as in
``bench/trace_reduce.py``, whose interval arithmetic this module reuses.

* group of a leaf op: the innermost stage in its name stack; ops under
  ``layers`` and no stage inside it are the layer scan's own slicing and
  write-back of the stacked weights and KV pool, ``layer_io``; ops under
  no stage are unscoped;
* a step: one execution of the step program (``jit__step_fn`` on the
  ``XLA Modules`` line) that starts in the stretch;
* idle by phase: device 0's idle time in the stretch, under the innermost
  ``engine.*`` span, else the innermost ``bench.*`` span, else none.
"""
from __future__ import annotations

import bisect
import dataclasses
import struct
from typing import Dict, Iterator, List, Optional, Tuple

from bench import trace_reduce as TRD

#: the program's device stages (``jax.named_scope``), outermost first
STAGES = ("embed", "layers", "qkv", "kv_append", "attn", "attn_out", "ffn",
          "lm_head", "sample")
#: group of the ops under ``layers`` and under no stage inside it
LAYER_IO = "layer_io"
#: the engine's host phases (``jax.profiler.TraceAnnotation``), in order
STEP_SPAN = "engine.step"
PHASES = ("engine.admit", "engine.plan", "engine.feed", "engine.dispatch",
          "engine.wait", "engine.emit")
ENGINE_PREFIX = "engine."
#: the step program's name on the ``XLA Modules`` line
STEP_MODULE = "jit__step_fn"
MODULES_LINE = "XLA Modules"
NO_SPAN = "none"
#: idle gaps the summary lists, longest first
N_GAPS = 10

#: metric groups: the stages whose device time each per-step metric sums
METRIC_GROUPS = {
    "kv_io": ("kv_append", LAYER_IO),
    "attn": ("attn",),
    "gemm": ("qkv", "attn_out", "ffn", "lm_head"),
    "sample": ("sample",),
}


# -- XSpace wire format --------------------------------------------------------


def _varint(buf, i: int) -> Tuple[int, int]:
    out, shift = 0, 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, i: int, end: int) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message; a value of wire
    type 2 is its (start, end) in ``buf``."""
    while i < end:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire == 1:
            value, i = struct.unpack_from("<d", buf, i)[0], i + 8
        elif wire == 5:
            value, i = None, i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield field, wire, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


@dataclasses.dataclass
class DeviceEvent:
    start_ns: float
    end_ns: float
    name: str          # the HLO instruction as the trace names it
    tf_op: str         # ``<op_name>:<op_type>``, or "" where it has none


@dataclasses.dataclass
class DevicePlane:
    name: str
    lines: Dict[str, List[DeviceEvent]]


def _metadata(buf, span, stat_names: Dict[int, str]) -> Tuple[str, str]:
    """(name, ``tf_op``) of one XEventMetadata; a string stat is stored
    inline or as a reference to the stat metadata that names it."""
    name, tf_op = "", ""
    for f, _, v in _fields(buf, *span):
        if f == 2:
            name = _text(buf, v)
        elif f == 5:
            sid, val = None, ""
            for sf, _, sv in _fields(buf, *v):
                if sf == 1:
                    sid = sv
                elif sf == 5:
                    val = _text(buf, sv)
                elif sf == 7:
                    val = stat_names.get(sv, "")
            if stat_names.get(sid) == "tf_op":
                tf_op = val
    return name, tf_op


def _map_entry(buf, span) -> Tuple[int, Optional[Tuple[int, int]]]:
    key, value = 0, None
    for f, _, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _plane(buf, span) -> DevicePlane:
    name = ""
    raw_lines, raw_meta, stat_names = [], [], {}
    for f, _, v in _fields(buf, *span):
        if f == 2:
            name = _text(buf, v)
        elif f == 3:
            raw_lines.append(v)
        elif f == 4:
            raw_meta.append(v)
        elif f == 5:
            key, value = _map_entry(buf, v)
            for sf, _, sv in _fields(buf, *value):
                if sf == 2:
                    stat_names[key] = _text(buf, sv)
    meta: Dict[int, Tuple[str, str]] = {}
    for v in raw_meta:
        key, value = _map_entry(buf, v)
        meta[key] = _metadata(buf, value, stat_names)
    lines: Dict[str, List[DeviceEvent]] = {}
    for v in raw_lines:
        lname, t0, events = "", 0, []
        for f, _, lv in _fields(buf, *v):
            if f == 2:
                lname = _text(buf, lv)
            elif f == 3:
                t0 = lv
            elif f == 4:
                events.append(lv)
        if lname not in (TRD.OPS_LINE, MODULES_LINE):
            continue
        out = lines.setdefault(lname, [])
        for ev in events:
            mid = off = dur = 0
            for f, _, ev_v in _fields(buf, *ev):
                if f == 1:
                    mid = ev_v
                elif f == 2:
                    off = ev_v
                elif f == 3:
                    dur = ev_v
            start = t0 + off * 1e-3
            mname, tf_op = meta.get(mid, ("", ""))
            out.append(DeviceEvent(start, start + dur * 1e-3, mname, tf_op))
    return DevicePlane(name, lines)


def device_planes(path: str) -> List[DevicePlane]:
    """The TPU planes of a trace, each with its ``XLA Ops`` and ``XLA
    Modules`` events."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = []
    for field, _, span in _fields(buf, 0, len(buf)):
        if field != 1:
            continue
        name = ""
        for f, _, v in _fields(buf, *span):
            if f == 2:
                name = _text(buf, v)
                break
        if name.startswith(TRD.DEVICE_PREFIX):
            out.append(_plane(buf, span))
    return out


# -- reduction -----------------------------------------------------------------

UNSCOPED = "unscoped"
Span = Tuple[str, float, float]


def op_path(tf_op: str) -> str:
    """``<op_name>:<op_type>`` -> ``<op_name>``."""
    return tf_op.rsplit(":", 1)[0]


def group_of(tf_op: str) -> str:
    """The innermost stage in an op's name stack; ``layer_io`` for an op
    under ``layers`` and no stage inside it; else ``unscoped``."""
    for part in reversed(op_path(tf_op).split("/")[:-1]):
        if part in STAGES:
            return LAYER_IO if part == "layers" else part
    return UNSCOPED


def host_spans(path: str) -> List[Span]:
    """Every ``engine.*`` and ``bench.*`` span on the host planes."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(TRD.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith((ENGINE_PREFIX, TRD.HOST_PREFIX)):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def window_of(spans: List[Span]) -> Optional[TRD.Interval]:
    for name, a, b in spans:
        if name == TRD.WINDOW_SPAN:
            return a, b
    return None


def leaf_ops(plane: DevicePlane, lo: float, hi: float):
    """The leaf ops of a device plane that overlap [lo, hi], clipped."""
    evs = [(e.start_ns, e.end_ns, e) for e in plane.lines.get(TRD.OPS_LINE,
                                                             [])
           if e.end_ns > lo and e.start_ns < hi]
    return [(max(a, lo), min(b, hi), e) for a, b, e in TRD.leaves(evs)]


def seconds_by(leaves, key) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for a, b, e in leaves:
        k = key(e)
        out[k] = out.get(k, 0.0) + (b - a) * 1e-9
    return out


def idle_by_phase(gaps: List[TRD.Interval], spans: List[Span]
                  ) -> Dict[str, float]:
    """Seconds of ``gaps`` under the innermost ``engine.*`` span, else
    the innermost other span, else under none (one sweep; spans nest)."""
    marks = sorted([(a, 1, i) for i, (_, a, _) in enumerate(spans)] +
                   [(b, 0, i) for i, (_, _, b) in enumerate(spans)])
    active: List[int] = []
    out: Dict[str, float] = {}

    def label() -> str:
        eng = [i for i in active if spans[i][0].startswith(ENGINE_PREFIX)]
        pool = eng or active
        return spans[max(pool, key=lambda i: spans[i][1])][0] if pool \
            else NO_SPAN

    def apply(mark):
        if mark[1]:
            active.append(mark[2])
        elif mark[2] in active:
            active.remove(mark[2])

    j = 0
    for a, b in gaps:
        while j < len(marks) and marks[j][0] <= a:
            apply(marks[j])
            j += 1
        t = a
        while j < len(marks) and marks[j][0] < b:
            k = label()
            out[k] = out.get(k, 0.0) + (marks[j][0] - t) * 1e-9
            t = marks[j][0]
            apply(marks[j])
            j += 1
        k = label()
        out[k] = out.get(k, 0.0) + (b - t) * 1e-9
    return out


def step_phases(spans: List[Span]) -> List[Tuple[Span, List[Span]]]:
    """Each ``engine.step`` span with the ``engine.*`` spans inside it,
    in order."""
    steps = [s for s in spans if s[0] == STEP_SPAN]
    inner = [s for s in spans if s[0] in PHASES]
    starts = [s[1] for s in inner]
    out = []
    for step in steps:
        i = bisect.bisect_left(starts, step[1])
        kids = []
        while i < len(inner) and inner[i][1] <= step[2]:
            kids.append(inner[i])
            i += 1
        out.append((step, kids))
    return out


def same_clock_share(modules: List[DeviceEvent], spans: List[Span]
                     ) -> Optional[float]:
    """Share of step-program executions that start after their step's
    ``engine.dispatch`` begins and end before its ``engine.wait`` ends
    (the step: the last dispatch begun before the program starts)."""
    dispatch = [s for s in spans if s[0] == "engine.dispatch"]
    wait = [s for s in spans if s[0] == "engine.wait"]
    d_starts = [s[1] for s in dispatch]
    w_starts = [s[1] for s in wait]
    if not modules:
        return None
    ok = 0
    for m in modules:
        i = bisect.bisect_right(d_starts, m.start_ns) - 1
        if i < 0:
            continue
        j = bisect.bisect_left(w_starts, dispatch[i][1])
        ok += j < len(wait) and m.end_ns <= wait[j][2]
    return ok / len(modules)


def clock_offset_bounds(modules: List[DeviceEvent], spans: List[Span]
                        ) -> Optional[TRD.Interval]:
    """The range [lo, hi] (ns) of constant shifts of the device clock
    under which every step program starts after its step's
    ``engine.dispatch`` begins and ends before its ``engine.wait`` ends
    (its step: the dispatch that begins nearest the program's start).
    0 in the range: the two clocks agree as recorded; lo > hi: no one
    shift reconciles them."""
    dispatch = [s for s in spans if s[0] == "engine.dispatch"]
    wait = [s for s in spans if s[0] == "engine.wait"]
    d_starts = [s[1] for s in dispatch]
    w_starts = [s[1] for s in wait]
    if not modules or not dispatch:
        return None
    lo, hi = -float("inf"), float("inf")
    for m in modules:
        i = bisect.bisect_left(d_starts, m.start_ns)
        i = min((k for k in (i - 1, i) if 0 <= k < len(dispatch)),
                key=lambda k: abs(d_starts[k] - m.start_ns))
        j = bisect.bisect_left(w_starts, dispatch[i][1])
        if j == len(wait):
            return None
        lo = max(lo, dispatch[i][1] - m.start_ns)
        hi = min(hi, wait[j][2] - m.end_ns)
    return lo, hi


@dataclasses.dataclass
class StageSummary:
    """What the program's stages, phases and spans read in the stretch."""

    window_s: float
    n_steps: int                    # step programs starting in the stretch
    group_s: Dict[str, float]       # leaf-op seconds by group, per device
    op_s: Dict[str, Tuple[float, str]]  # op -> (seconds, name stack)
    leaf_s: float                   # all leaf-op seconds, per device
    step_host_s: List[float]        # each engine.step minus its engine.wait
    idle_by_phase: Dict[str, float]  # device 0's idle seconds
    idle_gaps: List[Tuple[str, float]]  # longest first, by the span
    #                                      holding most of each
    same_clock: Optional[float]     # see same_clock_share
    clock_offset: Optional[TRD.Interval]  # see clock_offset_bounds

    def top_ops(self, n: int = 10) -> List[list]:
        """The ``n`` longest ops: [op, seconds, name stack]."""
        return [[k, s, p] for k, (s, p) in
                sorted(self.op_s.items(), key=lambda kv: -kv[1][0])[:n]]

    def dev_ms(self, metric: str) -> Optional[float]:
        """Device ms per step under the metric's groups."""
        if not self.n_steps:
            return None
        return sum(self.group_s.get(g, 0.0) for g in METRIC_GROUPS[metric]) \
            / self.n_steps * 1e3

    def unscoped_share(self) -> Optional[float]:
        """% of leaf-op time under no stage."""
        if self.leaf_s <= 0:
            return None
        return 100.0 * self.group_s.get(UNSCOPED, 0.0) / self.leaf_s

    def step_host_ms(self) -> Optional[float]:
        if not self.step_host_s:
            return None
        return sum(self.step_host_s) / len(self.step_host_s) * 1e3


def reduce(path: str) -> Optional[StageSummary]:
    """The stage summary of one trace; None when it holds no device plane
    or no window span."""
    spans = host_spans(path)
    window = window_of(spans)
    planes = device_planes(path)
    if window is None or not planes:
        return None
    lo, hi = window
    spans = [s for s in spans if s[0] != TRD.WINDOW_SPAN]
    group_s: Dict[str, float] = {}
    op_s: Dict[str, Tuple[float, str]] = {}
    for plane in planes:
        leaves = leaf_ops(plane, lo, hi)
        for k, v in seconds_by(leaves, lambda e: group_of(e.tf_op)).items():
            group_s[k] = group_s.get(k, 0.0) + v / len(planes)
        paths = {TRD.op_name(e.name): op_path(e.tf_op) for _, _, e in leaves}
        for k, v in seconds_by(leaves, lambda e: TRD.op_name(e.name)).items():
            op_s[k] = (op_s.get(k, (0.0, ""))[0] + v / len(planes), paths[k])
    ops0 = [(e.start_ns, e.end_ns) for e in planes[0].lines.get(
        TRD.OPS_LINE, [])]
    busy0 = TRD.union(TRD.clip(ops0, lo, hi))
    modules = [e for e in planes[0].lines.get(MODULES_LINE, [])
               if e.name.startswith(STEP_MODULE) and lo <= e.start_ns < hi]
    gaps = TRD.gaps_of(busy0, lo, hi)
    longest = []
    for g in sorted(gaps, key=lambda g: g[0] - g[1])[:N_GAPS]:
        by = idle_by_phase([g], spans)
        longest.append((max(by, key=by.get), (g[1] - g[0]) * 1e-9))
    host = []
    for step, kids in step_phases(spans):
        if lo <= step[1] and step[2] <= hi:
            wait = sum(b - a for n, a, b in kids if n == "engine.wait")
            host.append((step[2] - step[1] - wait) * 1e-9)
    return StageSummary(
        window_s=(hi - lo) * 1e-9, n_steps=len(modules), group_s=group_s,
        op_s=op_s,
        leaf_s=sum(group_s.values()), step_host_s=host,
        idle_by_phase=idle_by_phase(gaps, spans), idle_gaps=longest,
        same_clock=same_clock_share(modules, spans),
        clock_offset=clock_offset_bounds(modules, spans))
