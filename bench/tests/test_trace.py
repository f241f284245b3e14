"""Trace reduction: busy/idle union, kernel (module) time, idle gaps named by
the host span, on a small recorded trace."""
import pytest

from bench import trace

DEV = "/device:TPU:0"


def ev(plane, line, name, start, dur):
    return trace.Event(plane, line, name, start, dur)


def synthetic():
    """Window 0..100 ns. Device ops: [10,30) and [20,40) overlap, [60,70),
    and [95,120) runs past the window's end."""
    return [
        ev("/host:CPU", "python", "bench.window", 0, 100),
        ev("/host:CPU", "python", "bench.step", 0, 100),
        ev("/host:CPU", "python", "bench.masks", 40, 20),
        ev(DEV, trace.OPS_LINE, "fusion.1", 10, 20),
        ev(DEV, trace.OPS_LINE, "custom-call", 20, 20),
        ev(DEV, trace.OPS_LINE, "fusion.1", 60, 10),
        ev(DEV, trace.OPS_LINE, "copy", 95, 25),
        ev(DEV, trace.MODULES_LINE, "jit__sparse_ffn_segments_fused_pallas",
           20, 20),
        ev(DEV, trace.MODULES_LINE, "jit_other", 60, 10),
    ]


def test_busy_is_the_union_inside_the_window():
    r = trace.reduce_events(synthetic())
    assert r.window_s == pytest.approx(100e-9)
    # [10,40) + [60,70) + [95,100)
    assert r.busy_s == pytest.approx(45e-9)
    assert r.idle_share == pytest.approx(0.55)
    assert r.n_devices == 1


def test_kernel_time_and_top_ops():
    r = trace.reduce_events(synthetic())
    assert r.seconds_matching(("sparse_ffn_segments_fused",)) == \
        pytest.approx(20e-9)
    assert r.seconds_matching(("nothing",)) == 0
    assert r.top_ops()[0] == ["fusion.1", pytest.approx(30e-9)]


def test_idle_gaps_named_by_innermost_span():
    r = trace.reduce_events(synthetic())
    # gaps [0,10) and [70,95) under bench.step, [40,60) under bench.masks
    assert r.idle_by_span["bench.step"] == pytest.approx(35e-9)
    assert r.idle_by_span["bench.masks"] == pytest.approx(20e-9)
    assert r.top_idle(1) == [["bench.step", pytest.approx(35e-9)]]


def test_name_instants_nested_and_outside():
    spans = [(0, 100, "a"), (10, 20, "b"), (30, 40, "c")]
    assert trace.name_instants(spans, [15, 25, 35, 150, 5]) == \
        ["b", "a", "c", "no span", "a"]


def test_no_window_or_device_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce_events([e for e in synthetic()
                             if e.name != trace.WINDOW_SPAN])
    with pytest.raises(ValueError):
        trace.reduce_events([e for e in synthetic() if e.plane != DEV])


@pytest.mark.parametrize("name,label", [
    ("%copy.6 = f32[24,130,16,32,64]{1,4,3,2,0:T(8,128)} copy(f32[24,130]"
     "{1,0} %args_0_.1)", "%copy.6 copy"),
    ("%_paged_decode_pallas.1 = f32[4,32,1,64]{3,2,1,0:T(1,128)} "
     "custom-call(s32[4,41]{1,0:T(4,128)S(1)} %copy-done.1)",
     "%_paged_decode_pallas.1 custom-call"),
    ("fusion.1", "fusion.1"),
])
def test_op_label_keeps_name_and_opcode(name, label):
    assert trace.op_label(name) == label
