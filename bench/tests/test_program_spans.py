"""The program's spans and counters over a window: idle named on the serving
thread only, per-name seconds clipped to the window, and the readings of a
tiny decode cell with the recording tracer on."""
import time

import pytest

from bench import harness, program_spans, trace
from bench.tests import tinyroot

DEV = "/device:TPU:0"
HOST = "/host:CPU"
MAIN, WORKER = "python:0", "python:1"


def ev(plane, line, name, start, dur):
    return trace.Event(plane, line, name, start, dur)


def two_threads():
    """Window 0..100 ns on the serving line; device ops [10,30), [60,70).
    Nested spans open one after another, so each starts after its parent.
    The worker line's span covers every gap and must name none."""
    return [
        ev(HOST, MAIN, "bench.window", 0, 100),
        ev(HOST, MAIN, "bench.decode_step", 0, 100),
        ev(HOST, MAIN, "repro.decode_step", 1, 99),
        ev(HOST, MAIN, "repro.attention", 2, 33),
        ev(HOST, MAIN, "repro.sync", 30, 5),
        ev(HOST, MAIN, "repro.ffn", 35, 40),
        ev(HOST, MAIN, "bench.masks", 40, 10),
        ev(HOST, MAIN, "repro.sync", 42, 4),
        ev(HOST, MAIN, "repro.attention", 90, 30),   # runs past the window
        ev(HOST, WORKER, "repro.prefetch", -20, 140),
        ev(DEV, trace.OPS_LINE, "fusion.1", 10, 20),
        ev(DEV, trace.OPS_LINE, "fusion.2", 60, 10),
    ]


def test_idle_is_named_on_the_serving_line_only():
    r = program_spans.reduce_program(two_threads())
    # gaps [0,10) mid 5 -> attention, [30,60) mid 45 -> repro.sync
    # (innermost, inside bench.masks), [70,100) mid 85 -> repro.decode_step
    assert r.idle_by_span == {
        "repro.attention": pytest.approx(10e-9),
        "repro.sync": pytest.approx(30e-9),
        "repro.decode_step": pytest.approx(30e-9)}
    assert "repro.prefetch" not in r.idle_by_span
    assert r.window_s == pytest.approx(100e-9)


def test_span_seconds_and_counts_clipped_to_the_window():
    r = program_spans.reduce_program(two_threads())
    assert r.span_seconds["repro.attention"] == pytest.approx(43e-9)
    assert r.span_counts["repro.attention"] == 2
    assert r.span_seconds["repro.sync"] == pytest.approx(9e-9)
    assert r.span_counts["repro.sync"] == 2
    assert r.span_counts["repro.decode_step"] == 1
    assert not any(k.startswith("bench.") for k in r.span_seconds)
    # the worker's span is summed apart, clipped to the window
    assert r.other_seconds == {"repro.prefetch": pytest.approx(100e-9)}
    assert "repro.prefetch" not in r.span_seconds


def test_serving_line_needs_a_window():
    with pytest.raises(ValueError):
        program_spans.reduce_program(
            [e for e in two_threads() if e.name != trace.WINDOW_SPAN])


def test_ring_step_ms_and_counter_shares():
    ring = [{"ph": "X", "tid": 1, "name": "decode_step", "dur": 9000.0},
            {"ph": "X", "tid": 1, "name": "decode_step", "dur": 9000.0},
            {"ph": "X", "tid": 1, "name": "attention", "dur": 3000.0},
            {"ph": "X", "tid": 1, "name": "sync", "dur": 1000.0},
            {"ph": "X", "tid": 2, "name": "sync", "dur": 50000.0},
            {"ph": "M", "tid": 1, "name": "thread_name"}]
    got = program_spans.ring_step_ms(ring, 1)
    assert got["decode_steps"] == 2
    assert got["attention_host_ms"] == pytest.approx(1.5)
    assert got["sync_host_ms"] == pytest.approx(0.5)
    assert got["ffn_host_ms"] == 0.0
    assert program_spans.ring_step_ms(ring, 3) == {}
    shares = program_spans.counter_shares(
        {"offload.true_union_neurons": 16, "offload.served_neurons": 64,
         "offload.segment_rows": 80})
    assert shares == {"lookahead_precision": pytest.approx(25.0),
                      "segment_fill": pytest.approx(80.0)}
    assert program_spans.counter_shares({}) == {}


@pytest.fixture(scope="module")
def tiny_readings(tmp_path_factory):
    root = tinyroot.make(tmp_path_factory.mktemp("bench"))
    cell = harness.load_cell("tiny.decode", root)
    with program_spans.hooks() as cap:
        result = harness.run_cell(cell, 2**31 + 11, 1.0, False,
                                  time.monotonic())
    return result, program_spans.readings(cap)


def test_tiny_decode_cell_reads_every_program_reading(tiny_readings):
    result, got = tiny_readings
    assert result["correct"] is True
    assert got["decode_steps"] > 0
    for name in ("attention_host_ms", "ffn_host_ms", "sync_host_ms"):
        assert got[name] > 0, name
    c = got["counters"]
    assert 0 < c["offload.true_union_neurons"] \
        <= c["offload.served_neurons"] <= c["offload.segment_rows"]
    assert 0 < got["lookahead_precision"] <= 100
    assert 0 < got["segment_fill"] <= 100


def test_hooks_restore_the_harness(tiny_readings):
    from repro.obs import NULL_TRACER, get_tracer
    assert get_tracer() is NULL_TRACER
    assert trace.load_events.__module__ == "bench.trace"
    assert harness.ClosedLoop.run.__qualname__ == "ClosedLoop.run"
