"""The harness driven on the CPU at a tiny size: a cell, a mix and a metric
added as new files are picked up; a sound run is correct; the bfloat16
control is not."""
import json
import time

import pytest

from bench import harness
from bench.tests import tinyroot

PROBE = '''
def read(run):
    return float(run.window_tokens)
'''


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tinyroot.make(tmp_path_factory.mktemp("bench"))
    (root / "bench" / "metrics" / "tiny_probe.py").write_text(PROBE)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["per_layer"].append({"name": "tiny_probe", "unit": "tokens",
                              "better": "higher", "source": "host_clock",
                              "layer": "scheduler", "moves": "decode_tok_s",
                              "workloads": ["tiny.decode"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture(scope="module")
def decode_run(root):
    cell = harness.load_cell("tiny.decode", root)
    return cell, harness.run_cell(cell, 2**31 + 7, 1.0, False,
                                  time.monotonic())


@pytest.fixture(scope="module")
def control_run(root):
    cell = harness.load_cell("tiny.decode", root)
    return harness.run_cell(cell, 2**31 + 9, 1.0, False, time.monotonic(),
                            control=True)


def test_new_files_are_picked_up(root, decode_run):
    cell, result = decode_run
    assert cell.config["name"] == "tiny"
    assert cell.mix["loop"] == "closed"
    assert "tiny_probe" in [m["name"] for m in cell.per_layer]
    assert set(result["metrics"]) == {"decode_tok_s", "itl_p95_ms",
                                      "setup_s"}


def test_new_metric_is_read_and_silent_readers_left_out(decode_run):
    cell, _ = decode_run
    run = harness.Run(cell=cell, dims=None, peak=None, t0=0.0, t1=1.0,
                      setup_s=1.0, served=[], window_tokens=12, gaps=[],
                      n_mats=2, compiles_in_window=0, memory_peak_bytes=0)
    got = harness._metrics(run, trace=True)
    assert got == {"tiny_probe": {"value": 12.0, "unit": "tokens"}}


def test_sound_decode_run_is_correct(decode_run):
    cell, result = decode_run
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert list(result)[-1] == "checks"
    readings = result["readings"]
    assert readings["served_tokens"] > 0
    # the committed cell's checks, each read for the program
    assert set(cell.limits["checks"]) <= set(result["checks"])
    for name in cell.limits["checks"]:
        assert result["checks"][name]["value"] == readings[name]
        assert readings[name] <= result["checks"][name]["limit"]
    share = result["checks"]["activation_share_off"]
    assert share["limit"] == cell.config["assumed"]["activations"]["tolerance"]
    assert share["value"] <= share["limit"]


def test_sound_prefill_run_is_correct(root):
    cell = harness.load_cell("tiny.prefill", root)
    result = harness.run_cell(cell, 11, 1.0, False, time.monotonic())
    assert result["correct"] is True
    assert set(result["metrics"]) == {"ttft_p95_ms", "setup_s"}
    assert result["attempted"] == 20


def test_bfloat16_control_is_not_correct(root, control_run):
    """With `control`, the reference one precision step lower, bfloat16, is
    read in the program's place on the same served tokens, and decides
    `correct`: it fails the committed checks, while the program's own
    verdict on the same run stays true."""
    result = control_run
    readings = result["readings"]
    assert result["correct"] is False
    assert readings["program_correct"] is True
    limits = harness.load_cell("tiny.decode", root).limits["checks"]
    for name in limits:
        assert result["checks"][name]["value"] == readings["control_" + name]
    assert readings["control_logit_err_rms_vs_default"] > \
        limits["logit_err_rms_vs_default"]
    # the gap a second-best-token sampler would read, far over its limit
    assert readings["second_best_logit_gap_vs_default"] > \
        3 * limits["logit_gap_vs_default"]


def test_activation_share_off_its_tolerance_is_not_correct(root, decode_run):
    """A share off the configuration's tolerance fails the run, whatever the
    logits read."""
    cell, result = decode_run
    readings = dict(result["readings"])
    readings["token_share"] *= 1.5
    checks = harness.verdict(cell, readings, 0)
    assert not harness.passes(checks, readings["served_tokens"])
    assert harness.passes(harness.verdict(cell, result["readings"], 0),
                          readings["served_tokens"])
