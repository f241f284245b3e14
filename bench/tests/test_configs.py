"""A configuration is its file and the reference module it names: the
`program` block states the program's configuration, the reference lays out
the weights and gives the sizes the metric readers count with. A second
configuration of another shape (grouped-query attention, RMSNorm, a gated
SiLU FFN, tied embeddings), added to a checkout as new files only, runs
through the harness on the CPU."""
import dataclasses
import filecmp
import json
import time
import types

import jax
import pytest

from bench import harness, roofline
from bench.tests import tinyroot

OPT_FILES = [tinyroot.REPO / "bench" / "configs" / "opt-1.3b.json",
             tinyroot.DATA / "tiny.json"]
# a peak stated for the test: the CPU has none
PEAK = roofline.Peak(1e12, 1e11, 1e9, "stated for the test")


@pytest.mark.parametrize("path", OPT_FILES, ids=lambda p: p.name)
def test_opt_program_block_agrees_with_published_keys(path):
    c = json.loads(path.read_text())
    p = c["program"]
    assert p["arch"] == "opt-1.3b"
    assert (p["n_layers"], p["d_model"], p["d_ff"], p["vocab_size"]) == \
        (c["num_hidden_layers"], c["hidden_size"], c["ffn_dim"],
         c["vocab_size"])
    # OPT attends with every head for keys and values
    assert p["n_heads"] == p["n_kv_heads"] == c["num_attention_heads"]
    assert p["activation"] == c["activation_function"] == "relu"
    assert c["do_layer_norm_before"] and p["norm"] == "layernorm"
    assert p["rope_theta"] == c["rope_theta"] == c["assumed"]["rope_theta"]
    assert p["param_dtype"] == p["compute_dtype"] == c["torch_dtype"]


@pytest.mark.parametrize("path", OPT_FILES + [tinyroot.DATA / "tiny_gqa.json"],
                         ids=lambda p: p.name)
def test_reference_sizes_and_layout_are_the_programs(path):
    """The reference's sizes, read from the published keys, are the
    program's, and the weights it makes (shapes only, at full size) are laid
    out as the program's initialiser lays them."""
    from repro.models import build_model
    c = json.loads(path.read_text())
    ref = harness.load_module(_reference_path(c["reference"]))
    dims = ref.Dims.from_config(c)
    cfg = harness.program_config(c)
    assert (dims.n_layers, dims.d_model, dims.d_ff, dims.n_heads,
            dims.n_kv_heads, dims.head_dim, dims.vocab) == \
        (cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.n_heads, cfg.n_kv_heads,
         cfg.head_dim, cfg.vocab_size)
    shapes = jax.eval_shape(lambda: ref.make_params(dims, c, 0))
    harness.check_layout(build_model(cfg), shapes)
    assert dims.n_mats == len(shapes["stack"]["sub_0"]["ffn"])


def _reference_path(module: str):
    committed = tinyroot.REPO / "bench" / "reference" / f"{module}.py"
    return committed if committed.exists() else tinyroot.DATA / f"{module}.py"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinyroot.make(tmp_path_factory.mktemp("bench"))


def _run(root, cell_name, seed, control=False):
    """One run of a cell, and the `Run` its metric readers were given."""
    cell = harness.load_cell(cell_name, root)
    seen = []
    metrics = harness._metrics
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "_metrics",
                   lambda run, trace: seen.append(run) or metrics(run, trace))
        result = harness.run_cell(cell, seed, 1.0, False, time.monotonic(),
                                  control=control)
    return result, seen[0]


@pytest.fixture(scope="module")
def gqa_decode(root):
    return _run(root, "tiny-gqa.decode", 2**33 + 5)


@pytest.fixture(scope="module")
def gqa_prefill(root):
    return _run(root, "tiny-gqa.prefill", 2**31 + 17)


def test_second_configuration_is_new_files_only(root):
    """The checkout holds every committed file of the benchmark unchanged;
    the second configuration, its reference, mix and limits are added."""
    cmp = filecmp.dircmp(tinyroot.REPO / "bench", root / "bench",
                         ignore=["tests", "__pycache__"])

    def changed(d):
        return d.diff_files + d.left_only + [
            f for sub in d.subdirs.values() for f in changed(sub)]
    assert changed(cmp) == []
    for path in ("configs/tiny-gqa.json", "reference/gated_gqa.py",
                 "traffic/tiny_gqa_decode.json",
                 "limits/tiny-gqa.decode.json"):
        assert (root / "bench" / path).exists(), path


@pytest.mark.parametrize("cell_name", ["tiny-gqa.decode", "tiny-gqa.prefill"])
def test_second_configuration_is_correct(cell_name, gqa_decode, gqa_prefill):
    result, run = gqa_decode if "decode" in cell_name else gqa_prefill
    assert result["correct"] is True
    assert result["failed"] == 0 and result["readings"]["served_tokens"] > 0
    assert run.dims.n_kv_heads == run.dims.n_heads // 4
    assert run.n_mats == 3
    # no activation share is stated, so none is compared or read as 0
    assert "activation_share_off" not in result["checks"]
    assert "token_share" not in result["readings"]
    assert set(result["checks"]) == set(run.cell.limits["checks"]) | {
        "failed_requests"}


def test_second_configuration_bfloat16_control_is_not_correct(root):
    result, _ = _run(root, "tiny-gqa.decode", 2**31 + 23, control=True)
    readings = result["readings"]
    assert result["correct"] is False
    assert readings["program_correct"] is True
    limit = result["checks"]["logit_err_rms_vs_default"]["limit"]
    assert readings["control_logit_err_rms_vs_default"] > limit


# the second configuration by hand: 4 layers, d_model 64, 8 query heads of 8,
# 2 key/value heads, d_ff 256 with three matrices (up, gate, down), vocab 512
L, D, H, KV, HD, F, V = 4, 64, 8, 2, 8, 256, 512
LAYER_WEIGHTS = D * H * HD + 2 * D * KV * HD + H * HD * D + 3 * D * F


def _work_log(steps, unions=()):
    return types.SimpleNamespace(steps=steps, unions=list(unions))


def _trace(seconds):
    return types.SimpleNamespace(seconds_matching=lambda names: seconds)


def _reader(name):
    return harness.load_module(tinyroot.REPO / "bench" / "metrics"
                               / f"{name}.py")


def test_decode_readers_count_kv_heads_and_three_matrices(gqa_decode):
    _, run = gqa_decode
    steps = [[16, 31], [17, 32], [18, 33]]
    run = dataclasses.replace(run, peak=PEAK, work=_work_log(
        steps, unions=[(2, 40), (2, 56)]), trace=_trace(1e-3))
    flops = sum(L * (2 * LAYER_WEIGHTS + 4 * (p + 1) * D) + 2 * D * V
                for step in steps for p in step)
    assert _reader("mfu.decode").read(run) == pytest.approx(
        100 * flops / run.window_s / PEAK.flops, rel=1e-12)
    ctx = sum(p + 1 for step in steps for p in step)
    paged = max(L * 4 * ctx * H * HD / PEAK.flops,
                L * (2 * ctx * KV * HD * 4 + 2 * 6 * H * HD * 4)
                / PEAK.hbm_bytes_s)
    assert _reader("paged_decode_roofline").read(run) == pytest.approx(
        100 * paged / 1e-3, rel=1e-12)
    ffn = max(2 * 2 * 96 * 3 * D / PEAK.flops,
              (96 * 3 * D * 4 + 2 * 2 * 2 * D * 4) / PEAK.hbm_bytes_s)
    assert _reader("sparse_ffn_roofline").read(run) == pytest.approx(
        100 * ffn / 1e-3, rel=1e-12)


def test_prefill_reader_counts_kv_heads_and_three_matrices(gqa_prefill):
    _, run = gqa_prefill
    run = dataclasses.replace(run, peak=PEAK)
    prompts = [len(s.prompt) for s in run.served if s.handle.tokens]
    assert prompts
    flops = sum(L * (2 * LAYER_WEIGHTS * T + 4 * T * (T + 1) / 2 * D)
                + 2 * D * V for T in prompts)
    assert _reader("mfu.prefill").read(run) == pytest.approx(
        100 * flops / run.window_s / PEAK.flops, rel=1e-12)
