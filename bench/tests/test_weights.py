"""The activation-statistics transform hits its configured share."""
import json

import numpy as np
import pytest

from bench import harness, weights
from bench.tests import tinyroot

REF = harness.load_module(tinyroot.REPO / "bench" / "reference" / "opt.py")
TINY = json.loads((tinyroot.DATA / "tiny.json").read_text())


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_share_within_tolerance_on_fresh_tokens(seed):
    dims = REF.Dims.from_config(TINY)
    act = TINY["assumed"]["activations"]
    params = weights.make_params(REF, dims, act, seed)
    toks = np.random.default_rng(seed).integers(
        0, dims.vocab, (32, 128)).astype(np.int32)
    pos = np.full((32, 1), 127, np.int32)
    _, share = REF.forward_logits(params, [(toks, pos)], dims)[0]
    got = float(np.asarray(share).mean())
    assert abs(got - act["share"]) <= act["tolerance"] * act["share"], got


def test_same_seed_same_weights_and_wide_seeds_differ():
    dims = REF.Dims.from_config(TINY)
    act = TINY["assumed"]["activations"]
    a = weights.make_params(REF, dims, act, 2**32 + 1)
    b = weights.make_params(REF, dims, act, 2**32 + 1)
    c = weights.make_params(REF, dims, act, 1)
    w = lambda p: np.asarray(p["stack"]["sub_0"]["ffn"]["w_up"])
    assert np.array_equal(w(a), w(b))
    assert not np.array_equal(w(a), w(c))


def test_layout_is_the_programs():
    from repro.models import build_model
    dims = REF.Dims.from_config(TINY)
    params = weights.make_params(REF, dims, TINY["assumed"]["activations"], 0)
    harness.check_layout(build_model(harness.program_config(TINY)), params)
