"""Peaks table and the work functions the roofline and MFU readers use."""
import types

import pytest

from bench import harness, roofline
from bench.tests import tinyroot


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peak"):
        roofline.peak_for("TPU v9 imaginary")


def test_v5e_peak():
    p = roofline.peak_for("TPU v5 lite")
    assert (p.flops, p.hbm_bytes_s) == (197e12, 819e9)


@pytest.mark.parametrize("rows,union,n_mats,d", [(4, 1260, 2, 2048),
                                                 (1, 1, 3, 8)])
def test_sparse_ffn_work(rows, union, n_mats, d):
    w = roofline.sparse_ffn_work(rows, union, n_mats, d)
    assert w.flops == 2 * rows * union * n_mats * d
    assert w.bytes == union * n_mats * d * 4 + 2 * rows * d * 4


def test_sparse_ffn_is_memory_bound_at_decode_batch():
    w = roofline.sparse_ffn_work(4, 1260, 2, 2048)
    peak = roofline.peak_for("TPU v5 lite")
    assert w.bound(peak) == "memory"
    assert w.min_seconds(peak) == pytest.approx(w.bytes / 819e9)


def test_paged_decode_work_counts_filled_positions():
    # two slots at positions 9 and 19 attend to 10 and 20 keys
    w = roofline.paged_decode_work([9, 19], n_heads=4, n_kv_heads=2,
                                   head_dim=8)
    assert w.flops == 4 * 30 * 4 * 8
    assert w.bytes == 2 * 30 * 2 * 8 * 4 + 2 * 2 * 4 * 8 * 4


def test_paged_decode_roofline_reader_counts_kv_heads():
    """The reader counts the K/V bytes of `n_kv_heads` heads, not of every
    query head: 8 query heads share 2 key/value heads of 16."""
    reader = harness.load_module(tinyroot.REPO / "bench" / "metrics"
                                 / "paged_decode_roofline.py")
    dims = types.SimpleNamespace(n_layers=3, n_heads=8, n_kv_heads=2,
                                 head_dim=16)
    peak = roofline.Peak(1e12, 1e11, 1e9, "stated for the test")
    run = types.SimpleNamespace(
        dims=dims, peak=peak, work=types.SimpleNamespace(steps=[[9, 19]]),
        trace=types.SimpleNamespace(seconds_matching=lambda names: 2e-6))
    # 30 cached positions; K and V of 2 heads of 16 float32 each, plus q in
    # and the output out for 2 slots of 8 heads, in each of 3 layers
    nbytes = 3 * (2 * 30 * 2 * 16 * 4 + 2 * 2 * 8 * 16 * 4)
    flops = 3 * 4 * 30 * 8 * 16
    assert nbytes / peak.hbm_bytes_s > flops / peak.flops
    assert reader.read(run) == pytest.approx(
        100 * nbytes / peak.hbm_bytes_s / 2e-6, rel=1e-12)


def test_model_flops():
    kw = dict(n_layers=2, d_model=8, d_ff=32, n_heads=2, n_kv_heads=2,
              vocab=10, n_mats=2)
    per_layer = 8 * 4 * (2 + 4) + 2 * 4 * 8 + 2 * 8 * 32     # qkv, o, ffn
    assert roofline.decoder_layer_weights(8, 32, 2, 2, 2) == per_layer
    assert roofline.decode_token_flops(0, **kw) == \
        2 * (2 * per_layer + 4 * 8) + 2 * 8 * 10
    # causal prefill of 3 tokens: 6 query-key pairs
    assert roofline.prefill_request_flops(3, **kw) == \
        2 * (2 * per_layer * 3 + 4 * 6 * 8) + 2 * 8 * 10


def test_total_adds_work():
    a, b = roofline.Work(1.0, 2.0), roofline.Work(3.0, 4.0)
    assert roofline.total([a, b]) == roofline.Work(4.0, 6.0)
