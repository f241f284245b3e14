"""Reduction of a profiler trace to device busy time, kernel time and idle
gaps named by the host span the benchmark was in.

The trace is read with `jax.profiler.ProfileData` into plain `Event`s; the
reduction itself works on those alone, so it is tested on a small recorded
trace without a chip.

Device planes are `/device:TPU:<n>`. Their "XLA Ops" line holds one event per
operation that ran; busy time is the union of those intervals inside the
window. Host spans are the `bench.*` `TraceAnnotation`s the harness puts
around each layer's entry point (see `spans.py`); the window itself is the
`bench.window` span.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: int
    dur_ns: int

    @property
    def end_ns(self) -> int:
        return self.start_ns + self.dur_ns


def is_device_plane(plane: str) -> bool:
    return plane.startswith("/device:TPU:") or plane.startswith("/device:GPU:")


def load_events(trace_dir: str) -> List[Event]:
    """Every event of the newest `.xplane.pb` under `trace_dir`: device
    planes' op and module lines, and the host's `bench.*` spans."""
    import jax
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    out: List[Event] = []
    for plane in data.planes:
        device = is_device_plane(plane.name)
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                name = ev.name
                if not device and not name.startswith(SPAN_PREFIX):
                    continue
                out.append(Event(plane.name, line.name, name,
                                 int(ev.start_ns), int(ev.duration_ns)))
    return out


def merge(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Tuple[int, int]], lo: int,
         hi: int) -> List[Tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def window_bounds(events: Sequence[Event]) -> Tuple[int, int]:
    spans = [e for e in events if e.name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    w = max(spans, key=lambda e: e.dur_ns)
    return w.start_ns, w.end_ns


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                      # mean over the device planes
    n_devices: int
    op_seconds: Dict[str, float]       # device op name -> summed seconds
    module_seconds: Dict[str, float]   # XLA module name -> summed seconds
    idle_by_span: Dict[str, float]     # host span -> idle device seconds

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s if self.window_s else 0.0

    def top_ops(self, n: int = 10) -> List[List]:
        return [[k, v] for k, v in sorted(self.op_seconds.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def top_idle(self, n: int = 10) -> List[List]:
        return [[k, v] for k, v in sorted(self.idle_by_span.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def seconds_matching(self, needles: Sequence[str]) -> float:
        """Summed device seconds of the XLA modules whose name contains any
        of `needles` (each kernel runs as its own jitted module)."""
        return sum(v for k, v in self.module_seconds.items()
                   if any(n in k for n in needles))


def op_label(name: str) -> str:
    """A device op's event name is its whole HLO instruction; keep the
    instruction's name and its opcode (`%copy.6 copy`)."""
    head, _, rest = name.partition(" = ")
    m = re.search(r"\s([a-z][\w-]*)\(", rest)
    return f"{head} {m.group(1)}" if m else head


def name_instants(spans: Sequence[Tuple[int, int, str]],
                  instants: Sequence[int]) -> List[str]:
    """For each instant, the innermost host span covering it ("no span" if
    none). The harness's spans come from one thread and nest, so a sweep
    with a stack of open spans finds the innermost: the newest still open."""
    order = sorted(range(len(instants)), key=lambda i: instants[i])
    out = ["no span"] * len(instants)
    stack: List[Tuple[int, int, str]] = []
    j = 0
    spans = sorted(spans)
    for i in order:
        t = instants[i]
        while j < len(spans) and spans[j][0] <= t:
            while stack and stack[-1][1] <= spans[j][0]:
                stack.pop()
            stack.append(spans[j])
            j += 1
        while stack and stack[-1][1] <= t:
            stack.pop()
        if stack:
            out[i] = stack[-1][2]
    return out


def reduce_events(events: Sequence[Event]) -> Reduction:
    lo, hi = window_bounds(events)
    planes = sorted({e.plane for e in events if is_device_plane(e.plane)})
    if not planes:
        raise ValueError("the trace holds no device plane")
    host = sorted((e.start_ns, e.end_ns, e.name) for e in events
                  if not is_device_plane(e.plane)
                  and e.name.startswith(SPAN_PREFIX)
                  and e.name != WINDOW_SPAN)
    busy_total = 0
    ops: Dict[str, float] = collections.defaultdict(float)
    modules: Dict[str, float] = collections.defaultdict(float)
    idle: Dict[str, float] = collections.defaultdict(float)
    for plane in planes:
        mine = [e for e in events if e.plane == plane]
        op_ev = [e for e in mine if e.line == OPS_LINE]
        for e in op_ev:
            if e.end_ns > lo and e.start_ns < hi:
                ops[op_label(e.name)] += (min(e.end_ns, hi)
                                          - max(e.start_ns, lo)) / 1e9
        for e in mine:
            if e.line == MODULES_LINE and e.end_ns > lo and e.start_ns < hi:
                modules[e.name] += (min(e.end_ns, hi)
                                    - max(e.start_ns, lo)) / 1e9
        busy = clip(merge((e.start_ns, e.end_ns) for e in op_ev), lo, hi)
        busy_total += sum(e - s for s, e in busy)
        gaps, edge = [], lo
        for s, e in busy + [(hi, hi)]:
            if s > edge:
                gaps.append((edge, s))
            edge = max(edge, e)
        names = name_instants(host, [(a + b) // 2 for a, b in gaps])
        for (a, b), name in zip(gaps, names):
            idle[name] += (b - a) / 1e9 / len(planes)
    return Reduction(window_s=(hi - lo) / 1e9,
                     busy_s=busy_total / len(planes) / 1e9,
                     n_devices=len(planes), op_seconds=dict(ops),
                     module_seconds=dict(modules), idle_by_span=dict(idle))
