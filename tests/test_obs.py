"""Unified tracing + metrics subsystem (repro.obs).

The contract under test (ISSUE 10 acceptance): spans nest and order
correctly across threads into per-thread rings; ring wraparound keeps the
newest events and counts drops; the export is Perfetto/Chrome-loadable JSON
(every row has `ph`/`tid`, every body row has `ts`); the disabled
NULL_TRACER records exactly zero events so instrumented call sites are free
when tracing is off; a traced server run emits exactly one `decode` span
per emitted token; registered gauges read live object state; and
`request_timeline(handle)` reconstructs a request's phase breakdown.
"""
import collections
import json
import threading
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.obs import (NULL_TRACER, Counter, Gauge, Histogram,
                       MetricsRegistry, Tracer, disable_tracing,
                       enable_tracing, get_metrics, get_tracer,
                       request_timeline, set_metrics, set_tracer)
from repro.serving.engine import Request, build_offload_runtime
from repro.serving.server import InferenceServer


@pytest.fixture
def tracer():
    """A fresh recording tracer installed globally; always restored."""
    tr = enable_tracing(capacity_per_thread=4096)
    yield tr
    disable_tracing()


@pytest.fixture
def registry():
    prev = get_metrics()
    reg = MetricsRegistry()
    set_metrics(reg)
    yield reg
    set_metrics(prev)


def _setup(seed=0, vocab=128):
    cfg = get_config("opt-350m", reduced=True, d_model=64, d_ff=256,
                     n_layers=2, vocab_size=vocab, activation="relu")
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(seed))
    return cfg, model, params


# -- tracer core -------------------------------------------------------------

def test_span_nesting_and_ordering(tracer):
    """A child span closes before its parent, so the parent's X event has an
    earlier ts and a dur that covers the child's interval."""
    with tracer.span("outer", depth=0):
        time.sleep(0.001)
        with tracer.span("inner") as sp:
            sp.set(depth=1)
            time.sleep(0.001)
        time.sleep(0.001)
    evs = {e["name"]: e for e in tracer.events() if e["ph"] == "X"}
    outer, inner = evs["outer"], evs["inner"]
    assert outer["ts"] < inner["ts"]
    assert outer["ts"] + outer["dur"] >= inner["ts"] + inner["dur"]
    assert inner["args"]["depth"] == 1
    body = [e for e in tracer.events() if e["ph"] != "M"]
    assert body == sorted(body, key=lambda e: e["ts"])


def test_spans_from_threads_get_distinct_tids(tracer):
    def work(i):
        with tracer.span("job", worker=i):
            time.sleep(0.002)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    jobs = [e for e in tracer.events() if e["name"] == "job"]
    assert len(jobs) == 3
    assert len({e["tid"] for e in jobs}) == 3
    meta_tids = {e["tid"] for e in tracer.events() if e["ph"] == "M"}
    assert {e["tid"] for e in jobs} <= meta_tids


def test_spans_from_exited_threads_are_kept(tracer):
    """A thread that starts after another exited may get its ident; the
    finished thread's events must survive."""
    for i in range(4):
        t = threading.Thread(target=lambda: tracer.instant("job"))
        t.start()
        t.join()
    jobs = [e for e in tracer.events() if e["name"] == "job"]
    assert len(jobs) == 4
    assert len({e["tid"] for e in jobs}) == 4


def test_ring_buffer_wraparound_keeps_newest():
    tr = Tracer(capacity_per_thread=8)
    for i in range(20):
        tr.instant("ev", i=i)
    assert tr.n_events == 20          # total recorded
    assert tr.dropped == 12
    kept = [e["args"]["i"] for e in tr.events() if e["ph"] == "i"]
    assert kept == list(range(12, 20))  # newest 8, oldest-first order


def test_complete_and_virtual_tracks(tracer):
    t0 = tracer.now()
    time.sleep(0.001)
    t1 = tracer.now()
    tracer.complete("work", t0, t1, track="req 7", uid=7)
    ev = next(e for e in tracer.events() if e["name"] == "work")
    assert ev["tid"] >= 1_000_000     # virtual track lane
    meta = next(e for e in tracer.events()
                if e["ph"] == "M" and e["tid"] == ev["tid"])
    assert meta["args"]["name"] == "req 7"
    assert ev["dur"] == pytest.approx(t1 - t0)


def test_perfetto_export_schema(tracer, tmp_path):
    with tracer.span("a"):
        tracer.instant("mark", k=1)
    tracer.counter("ctr", x=1.0, y=2.0)
    path = tmp_path / "trace.json"
    tracer.export(str(path))
    doc = json.loads(path.read_text())   # loads as plain JSON
    assert "traceEvents" in doc and doc["displayTimeUnit"] == "ms"
    for ev in doc["traceEvents"]:
        assert "ph" in ev and "tid" in ev and "pid" in ev
        if ev["ph"] != "M":
            assert "ts" in ev
    phs = {e["ph"] for e in doc["traceEvents"]}
    assert {"X", "i", "C", "M"} <= phs
    inst = next(e for e in doc["traceEvents"] if e["ph"] == "i")
    assert inst["s"] == "t"


def test_disabled_tracer_records_exactly_zero():
    assert get_tracer() is NULL_TRACER   # module default
    with get_tracer().span("x", a=1) as sp:
        sp.set(b=2)                      # no-op, never raises
    get_tracer().instant("y")
    get_tracer().counter("z", v=1.0)
    get_tracer().complete("w", 0.0, 1.0)
    assert get_tracer().n_events == 0
    assert get_tracer().dropped == 0
    assert get_tracer().export() == []
    assert not get_tracer().enabled


def test_set_tracer_returns_previous(tracer):
    prev = set_tracer(NULL_TRACER)
    assert prev is tracer
    set_tracer(tracer)
    assert get_tracer() is tracer


# -- metrics -----------------------------------------------------------------

def test_counter_gauge_histogram_snapshot(registry):
    registry.counter("reqs").inc()
    registry.counter("reqs").inc(4)      # create-or-get: same counter
    registry.gauge("depth").set(3.0)
    h = registry.histogram("lat")
    for v in (0.5, 1.5, 6.0, 0.0):
        h.observe(v)
    snap = registry.snapshot()
    assert snap["counters"]["reqs"] == 5
    assert snap["gauges"]["depth"] == 3.0
    hs = snap["histograms"]["lat"]
    assert hs["count"] == 4 and hs["max"] == 6.0 and hs["min"] == 0.0
    assert hs["buckets"]["zero"] == 1    # v <= 0 sentinel bucket
    assert sum(hs["buckets"].values()) == 4


def test_histogram_log_buckets():
    h = Histogram("b")
    h.observe(1.0)      # frexp exp 1
    h.observe(1.9)      # same bucket
    h.observe(2.0)      # next bucket
    assert len([k for k in h.snapshot()["buckets"] if k != "zero"]) == 2


def test_registered_gauge_reads_live_state(registry):
    state = {"v": 1.0}
    registry.register_gauge("live", lambda: state["v"])
    assert registry.snapshot()["gauges"]["live"] == 1.0
    state["v"] = 9.0
    assert registry.snapshot()["gauges"]["live"] == 9.0
    registry.register_gauge("boom", lambda: 1 / 0)
    assert registry.snapshot()["gauges"]["boom"] is None   # failure -> None


def test_metrics_delta(registry):
    registry.counter("n").inc(2)
    registry.gauge("g").set(1.0)
    prev = registry.snapshot()
    registry.counter("n").inc(3)
    registry.gauge("g").set(7.0)
    d = registry.delta(prev)
    assert d["counters"]["n"] == 3       # counters subtract
    assert d["gauges"]["g"] == 7.0       # gauges report current


# -- server integration ------------------------------------------------------

def test_server_one_decode_span_per_token(tracer, registry, rng):
    cfg, model, params = _setup()
    server = InferenceServer(model, params, max_slots=2, max_len=64)
    reqs = [Request(uid=i, prompt=rng.integers(0, 128, 6 + 2 * i).astype(np.int32),
                    max_new_tokens=4 + i) for i in range(3)]
    try:
        for r in reqs:
            server.submit(r)
        results = server.drain()
    finally:
        server.close()
    evs = tracer.events()
    decode = [e for e in evs if e["name"] == "decode" and e["ph"] == "X"]
    assert len(decode) == server.stats.tokens_emitted
    assert server.stats.tokens_emitted == sum(len(r.tokens) for r in results)
    # every request has its own lane with a prefill and a retire
    for r in reqs:
        lane = [e for e in decode if e["args"]["uid"] == r.uid]
        assert len(lane) == next(
            len(x.tokens) for x in results if x.uid == r.uid)
    assert sum(1 for e in evs if e["name"] == "retire") == 3
    # registered server gauges read the final stats
    snap = registry.snapshot()
    assert snap["gauges"]["server.tokens_emitted"] == server.stats.tokens_emitted
    assert snap["histograms"]["server.step_seconds"]["count"] == \
        server.stats.decode_steps


def test_offload_trace_shows_prefetch_overlap(tracer, registry, rng):
    """Prefetch-worker read spans run on their own lane and at least one
    intersects a serving-thread decode_step span in wall time."""
    cfg, model, params = _setup()
    rt = build_offload_runtime(model, params,
                               rng=np.random.default_rng(7),
                               train_lookahead=True)
    server = InferenceServer(model, params, max_slots=2, max_len=64,
                             mode="offload", offload=rt, prefetch=True)
    try:
        for i in range(2):
            server.submit(Request(
                uid=i, prompt=rng.integers(0, 128, 8).astype(np.int32),
                max_new_tokens=5))
        server.drain()
    finally:
        server.close()
    evs = tracer.events()
    pf = [(e["ts"], e["ts"] + e["dur"], e["tid"]) for e in evs
          if e["name"] == "prefetch" and e["ph"] == "X"]
    ds = [(e["ts"], e["ts"] + e["dur"], e["tid"]) for e in evs
          if e["name"] == "decode_step"]
    assert pf and ds
    assert len({p[2] for p in pf} & {d[2] for d in ds}) == 0  # separate lanes
    assert any(p[0] < d[1] and d[0] < p[1] for p in pf for d in ds)
    # the IOScheduler's measured counter track rode along
    assert any(e["ph"] == "C" and e["name"] == "io_measured_ms" for e in evs)
    # scheduler gauges registered by the server match its summary
    snap = registry.snapshot()
    summ = server.scheduler.summary()
    assert snap["gauges"]["scheduler.tokens"] == summ["tokens"]
    assert snap["gauges"]["scheduler.measured_overlap_efficiency"] == \
        pytest.approx(summ["measured_overlap_efficiency"])


def test_request_timeline(tracer, registry, rng):
    cfg, model, params = _setup()
    server = InferenceServer(model, params, max_slots=1, max_len=64)
    req = Request(uid=0, prompt=rng.integers(0, 128, 8).astype(np.int32),
                  max_new_tokens=5)
    try:
        handle = server.submit(req)
        server.drain()
        tl = server.request_timeline(handle)
    finally:
        server.close()
    assert tl["uid"] == 0 and tl["n_tokens"] == len(handle.tokens)
    assert set(tl["phases"]) == {"queued", "prefill", "decode"}
    for ph in tl["phases"].values():
        assert ph["end"] >= ph["start"] >= 0.0
    assert tl["ttft"] is not None and tl["total"] >= tl["ttft"]
    assert len(tl["tokens"]) == tl["n_tokens"]
    assert tl["itl"]["count"] == tl["n_tokens"] - 1
    # the tracer slice only contains this request's spans
    assert tl["spans"] and all(
        e["args"]["uid"] == 0 for e in tl["spans"])


def test_disabled_server_run_emits_nothing(registry, rng):
    """With the null tracer installed (the default), a full server run
    records zero events — the disabled path costs only no-op calls."""
    assert get_tracer() is NULL_TRACER
    cfg, model, params = _setup()
    server = InferenceServer(model, params, max_slots=1, max_len=64)
    try:
        server.submit(Request(uid=0,
                              prompt=rng.integers(0, 128, 6).astype(np.int32),
                              max_new_tokens=3))
        server.drain()
    finally:
        server.close()
    assert get_tracer().n_events == 0


# -- profiler clock ------------------------------------------------------------

class _AnnotationRecorder:
    """Stands in for `jax.profiler.TraceAnnotation`: records each name it
    is opened with, and how many are still open."""
    opened: list = []
    depth = 0

    def __init__(self, name, **kw):
        assert not kw, "annotations carry no args"
        self.name = name

    def __enter__(self):
        type(self).opened.append(self.name)
        type(self).depth += 1
        return self

    def __exit__(self, *exc):
        type(self).depth -= 1
        return False


@pytest.fixture
def annotations(monkeypatch):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _AnnotationRecorder)
    _AnnotationRecorder.opened = []
    _AnnotationRecorder.depth = 0
    return _AnnotationRecorder


def _offload_run(seed=3):
    """A tiny paged offload server with the prefetch worker, two requests
    to the end; returns the runtime."""
    cfg, model, params = _setup()
    rt = build_offload_runtime(model, params, rng=np.random.default_rng(7),
                               train_lookahead=True)
    server = InferenceServer(model, params, max_slots=2, max_len=64,
                             mode="offload", offload=rt, prefetch=True,
                             page_size=8, num_pages=16)
    rng = np.random.default_rng(seed)
    try:
        for i in range(2):
            server.submit(Request(
                uid=i, prompt=rng.integers(0, 128, 8 + i).astype(np.int32),
                max_new_tokens=4))
        server.drain()
    finally:
        server.close()
    return rt


def test_spans_open_balanced_repro_annotations(annotations, registry):
    """Every span() (and only span()) of the recording tracer opens a
    `repro.<name>` annotation, balanced, on whichever thread ran it."""
    tr = enable_tracing()
    try:
        _offload_run()
    finally:
        disable_tracing()
    assert annotations.depth == 0
    spans = collections.Counter(
        "repro." + e["name"] for e in tr.events()
        if e["ph"] == "X" and e["tid"] < 1_000_000
        and e["name"] != "decode")          # decode: complete() only
    assert collections.Counter(annotations.opened) == spans
    for name in ("decode_step", "attention", "ffn", "sync", "embed",
                 "unembed", "stage_accounting", "prefill", "page_write",
                 "prefetch", "step"):
        assert "repro." + name in spans, name
    # 2 dense layers: a mask read per layer, a lookahead read for every
    # layer but the last, the logits read once per step
    steps = spans["repro.decode_step"]
    assert spans["repro.attention"] == spans["repro.ffn"] == 2 * steps
    assert spans["repro.sync"] == (2 + 1 + 1) * steps


def test_disabled_offload_run_records_and_annotates_nothing(annotations,
                                                            registry):
    assert get_tracer() is NULL_TRACER
    _offload_run()
    assert get_tracer().n_events == 0
    assert annotations.opened == []


def test_offload_counters_bound_each_other(registry):
    """The true activated union is inside what the segment kernel serves,
    which is inside the segment rows it reads."""
    rt = _offload_run()
    assert rt.ffn_kernel == "segments"
    c = registry.snapshot()["counters"]
    true, served, rows = (c["offload.true_union_neurons"],
                          c["offload.served_neurons"],
                          c["offload.segment_rows"])
    assert 0 < true <= served <= rows
    assert rows % rt.engine_cfg.kernel_seg_size == 0
