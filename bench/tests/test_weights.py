"""The activation-statistics transform hits its configured share, and the
weights of a seed stay what they were."""
import hashlib
import json

import jax
import numpy as np
import pytest

from bench import harness
from bench.tests import tinyroot

REF = harness.load_module(tinyroot.REPO / "bench" / "reference" / "opt.py")
TINY = json.loads((tinyroot.DATA / "tiny.json").read_text())
# sha256 of every leaf's bytes, with its shape and dtype, for two seeds (one
# wider than 32 bits), as the weights were made before the OPT shaping moved
# behind the reference module
HASHES = json.loads((tinyroot.DATA / "tiny_weights_sha256.json").read_text())


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_share_within_tolerance_on_fresh_tokens(seed):
    dims = REF.Dims.from_config(TINY)
    act = TINY["assumed"]["activations"]
    params = REF.make_params(dims, TINY, seed)
    toks = np.random.default_rng(seed).integers(
        0, dims.vocab, (32, 128)).astype(np.int32)
    pos = np.full((32, 1), 127, np.int32)
    _, share = REF.forward_logits(params, [(toks, pos)], dims)[0]
    got = float(np.asarray(share).mean())
    assert abs(got - act["share"]) <= act["tolerance"] * act["share"], got


def test_same_seed_same_weights_and_wide_seeds_differ():
    dims = REF.Dims.from_config(TINY)
    a = REF.make_params(dims, TINY, 2**32 + 1)
    b = REF.make_params(dims, TINY, 2**32 + 1)
    c = REF.make_params(dims, TINY, 1)
    w = lambda p: np.asarray(p["stack"]["sub_0"]["ffn"]["w_up"])
    assert np.array_equal(w(a), w(b))
    assert not np.array_equal(w(a), w(c))


@pytest.mark.parametrize("seed", sorted(HASHES, key=int))
def test_weights_bitwise_as_committed(seed):
    params = REF.make_params(REF.Dims.from_config(TINY), TINY, int(seed))
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    got = {jax.tree_util.keystr(k): {
        "shape": list(v.shape), "dtype": str(v.dtype),
        "sha256": hashlib.sha256(np.ascontiguousarray(v).tobytes()).hexdigest()}
        for k, v in leaves}
    assert got == HASHES[seed]


def test_layout_is_the_programs():
    from repro.models import build_model
    dims = REF.Dims.from_config(TINY)
    params = REF.make_params(dims, TINY, 0)
    harness.check_layout(build_model(harness.program_config(TINY)), params)
