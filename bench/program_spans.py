"""The program's own spans and counters (`repro.obs`) over a benchmark window.

  python bench/program_spans.py --workload <cell> --seed <n> --seconds <s> \
      [--trace 0|1]

Runs one cell as `run.py` does (`harness.run_cell`), with two additions
around the window: the program's recording tracer (`enable_tracing()`,
whose spans annotate the profiler's trace), and snapshots of the metrics
registry at both of the window's edges. It prints the harness's result
line with a `program` entry added:

- per decode step, the host seconds of the serving thread's `repro.*` spans
  (`attention`, `ffn`, `sync`, `embed`, `unembed`, `stage_accounting`, ...),
  from the tracer's ring;
- from the counters' window deltas, `lookahead_precision` (true activated
  union ÷ neurons served) and `segment_fill` (neurons served ÷ segment rows
  read);
- with `--trace 1`, the device's idle gaps named by the innermost `bench.*`
  or `repro.*` span on the serving thread (the profiler line that holds
  `bench.window`), the serving thread's `repro.*` seconds and counts clipped
  to the window, and the other threads' `repro.*` seconds apart (they never
  name a gap).

`--trace 0` against `run.py --trace 0` on the same seed measures what the
recording tracer costs end to end. The result line's metrics are
computed as in `run.py`; nothing here changes them.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import glob
import os
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

T_START = time.monotonic()

ROOT = Path(__file__).resolve().parent.parent
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace  # noqa: E402

PROGRAM_PREFIX = "repro."
HOST_PREFIXES = (trace.SPAN_PREFIX, PROGRAM_PREFIX)
# per-step host seconds reported by name (ms per decode step)
STEP_SPANS = ("attention", "ffn", "sync", "embed", "unembed",
              "stage_accounting")
COUNTERS = ("offload.true_union_neurons", "offload.served_neurons",
            "offload.segment_rows")


# -- the profiler trace, with host lines kept apart ------------------------------

def load_events(trace_dir: str) -> List[trace.Event]:
    """As `trace.load_events`, but keeping `repro.*` host spans beside the
    `bench.*` ones, and naming each host line by its index in the plane:
    the profiler names every Python thread's line alike."""
    import jax
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    out: List[trace.Event] = []
    for plane in data.planes:
        device = trace.is_device_plane(plane.name)
        for i, line in enumerate(plane.lines):
            if device and line.name not in (trace.OPS_LINE,
                                             trace.MODULES_LINE):
                continue
            label = line.name if device else f"{line.name}:{i}"
            for ev in line.events:
                if not device and not ev.name.startswith(HOST_PREFIXES):
                    continue
                out.append(trace.Event(plane.name, label, ev.name,
                                       int(ev.start_ns), int(ev.duration_ns)))
    return out


@dataclasses.dataclass
class ProgramReduction:
    window_s: float
    idle_by_span: Dict[str, float]     # serving-thread innermost span -> s
    span_seconds: Dict[str, float]     # serving thread, repro.* -> s
    span_counts: Dict[str, int]        # serving thread, repro.* -> count
    other_seconds: Dict[str, float]    # other threads, repro.* -> s

    def top_idle(self, n: int = 12) -> List[List]:
        return [[k, v] for k, v in sorted(self.idle_by_span.items(),
                                          key=lambda kv: -kv[1])[:n]]


def serving_line(events: Sequence[trace.Event]) -> Tuple[str, str]:
    """(plane, line) of the thread that ran the window."""
    spans = [e for e in events if e.name == trace.WINDOW_SPAN]
    if not spans:
        raise ValueError(f"the trace holds no {trace.WINDOW_SPAN!r} span")
    w = max(spans, key=lambda e: e.dur_ns)
    return w.plane, w.line


def _clipped_s(e: trace.Event, lo: int, hi: int) -> float:
    return max(0, min(e.end_ns, hi) - max(e.start_ns, lo)) / 1e9


def reduce_program(events: Sequence[trace.Event]) -> ProgramReduction:
    """Idle device time named by the serving thread's innermost span of
    either prefix, and the `repro.*` spans' seconds and counts in the
    window, the serving thread's apart from the other threads'."""
    lo, hi = trace.window_bounds(events)
    line = serving_line(events)
    planes = sorted({e.plane for e in events
                     if trace.is_device_plane(e.plane)})
    if not planes:
        raise ValueError("the trace holds no device plane")
    host = [e for e in events if not trace.is_device_plane(e.plane)
            and e.name != trace.WINDOW_SPAN]
    mine = [e for e in host if (e.plane, e.line) == line]
    spans = sorted((e.start_ns, e.end_ns, e.name) for e in mine)
    idle: Dict[str, float] = collections.defaultdict(float)
    for plane in planes:
        ops = [(e.start_ns, e.end_ns) for e in events
               if e.plane == plane and e.line == trace.OPS_LINE]
        busy = trace.clip(trace.merge(ops), lo, hi)
        gaps, edge = [], lo
        for s, e in busy + [(hi, hi)]:
            if s > edge:
                gaps.append((edge, s))
            edge = max(edge, e)
        names = trace.name_instants(spans, [(a + b) // 2 for a, b in gaps])
        for (a, b), name in zip(gaps, names):
            idle[name] += (b - a) / 1e9 / len(planes)
    seconds: Dict[str, float] = collections.defaultdict(float)
    counts: Dict[str, int] = collections.defaultdict(int)
    other: Dict[str, float] = collections.defaultdict(float)
    for e in host:
        if not e.name.startswith(PROGRAM_PREFIX) or e.end_ns <= lo \
                or e.start_ns >= hi:
            continue
        if (e.plane, e.line) == line:
            seconds[e.name] += _clipped_s(e, lo, hi)
            counts[e.name] += 1
        else:
            other[e.name] += _clipped_s(e, lo, hi)
    return ProgramReduction(window_s=(hi - lo) / 1e9, idle_by_span=dict(idle),
                            span_seconds=dict(seconds),
                            span_counts=dict(counts),
                            other_seconds=dict(other))


# -- readings over the window ------------------------------------------------------

def ring_step_ms(events: Sequence[dict], thread: int) -> Dict[str, float]:
    """Per decode step, the summed milliseconds of each `STEP_SPANS` span
    the tracer's ring holds for `thread` (its Perfetto export)."""
    mine = [e for e in events if e.get("ph") == "X" and e["tid"] == thread]
    steps = sum(e["name"] == "decode_step" for e in mine)
    if not steps:
        return {}
    total: Dict[str, float] = collections.defaultdict(float)
    for e in mine:
        if e["name"] in STEP_SPANS:
            total[e["name"]] += e["dur"]
    out = {f"{n}_host_ms": total[n] / 1e3 / steps for n in STEP_SPANS}
    out["decode_steps"] = steps
    return out


def counter_shares(delta: Dict[str, int]) -> Dict[str, float]:
    """`lookahead_precision` and `segment_fill` (%) from the counters'
    window deltas; a share whose denominator did not move is left out."""
    true, served, rows = (delta.get(k, 0) for k in COUNTERS)
    out = {}
    if served:
        out["lookahead_precision"] = 100.0 * true / served
    if rows:
        out["segment_fill"] = 100.0 * served / rows
    return out


@dataclasses.dataclass
class Capture:
    """What the hooks saw: the tracer's ring, the counters' window deltas,
    the serving thread's id, and the extended profiler events."""
    ring: List[dict] = dataclasses.field(default_factory=list)
    counters: Dict[str, int] = dataclasses.field(default_factory=dict)
    thread: Optional[int] = None
    events: Optional[List[trace.Event]] = None


@contextlib.contextmanager
def hooks():
    """Wrap the harness's two loops' `run` (the window) with the recording
    tracer and the registry snapshots, and its trace loader with `load_events`;
    restore everything on exit."""
    import threading
    from bench import harness
    from repro.obs import disable_tracing, enable_tracing, get_metrics
    cap = Capture()
    undo = []

    def wrap_run(cls):
        run = cls.run

        def timed(self, seconds, clock):
            reg = get_metrics()
            tracer = enable_tracing()
            before = reg.snapshot()
            try:
                return run(self, seconds, clock)
            finally:
                cap.counters = reg.delta(before)["counters"]
                disable_tracing()
                cap.ring = tracer.events()
                cap.thread = threading.get_native_id()
        cls.run = timed
        undo.append(lambda: setattr(cls, "run", run))

    wrap_run(harness.ClosedLoop)
    wrap_run(harness.OpenLoop)
    load = trace.load_events

    def loading(trace_dir):
        cap.events = load_events(trace_dir)
        return load(trace_dir)
    trace.load_events = loading
    undo.append(lambda: setattr(trace, "load_events", load))
    try:
        yield cap
    finally:
        while undo:
            undo.pop()()


def readings(cap: Capture) -> Dict[str, object]:
    out: Dict[str, object] = dict(counter_shares(cap.counters))
    out["counters"] = {k: cap.counters.get(k, 0) for k in COUNTERS}
    if cap.thread is not None:
        out.update(ring_step_ms(cap.ring, cap.thread))
    if cap.events is not None:
        r = reduce_program(cap.events)
        out["idle_gaps"] = r.top_idle()
        out["span_seconds"] = r.span_seconds
        out["span_counts"] = r.span_counts
        out["other_thread_seconds"] = r.other_seconds
    return out


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    cell = harness.load_cell(args.workload)
    harness.enable_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        harness.log(f"needs {cell.chips} TPU chip(s); JAX found "
                    f"{len(devices)} {devices[0].platform} device(s)")
        return 2
    with hooks() as cap:
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), T_START)
    result["program"] = readings(cap)
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
