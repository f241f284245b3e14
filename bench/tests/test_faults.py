"""Each fault the served cells can have, planted under the timed path of a
tiny run on the CPU (the harness's look for a chip skipped), turns
`correct` false under the committed cells' checks. One chip serves each
cell, so there is no exchange between chips to leave out."""
import time

import pytest

from bench import harness
from bench.tests import tinyroot


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinyroot.make(tmp_path_factory.mktemp("bench"))


def _correct(root, cell_name):
    cell = harness.load_cell(cell_name, root)
    return harness.run_cell(cell, 2**31 + 3, 1.0, False,
                            time.monotonic())["correct"]


def altered_token(mp):
    """A token altered where it is produced: the sampler serves the token
    after the one the logits put first."""
    from repro.serving.server import InferenceServer
    sample = InferenceServer._sample_row
    mp.setattr(InferenceServer, "_sample_row",
               lambda self, h, row: (sample(self, h, row) + 1) % row.shape[-1])


def second_best_token(mp):
    """A token altered where it is produced, as near as it can be: the
    sampler serves the second-best token of each logits row. Every row the
    program computes is then conditioned on the tokens it served, so only the
    gap of the served token can see it."""
    import numpy as np
    from repro.serving.server import InferenceServer
    mp.setattr(InferenceServer, "_sample_row",
               lambda self, h, row: int(np.argsort(np.asarray(row))[-2]))


def state_unchanged(mp):
    """A step that returns its state unchanged: decode never writes the new
    token's keys and values into the paged cache."""
    from repro.models import transformer
    mp.setattr(transformer, "paged_kv_write_rows",
               lambda cache, k, v, position, page_tables: cache)


def half_batch(mp):
    """Half of the batch left out: the offload FFN serves only the first
    half of the decode slots."""
    from repro.serving.engine import OffloadedFFNRuntime
    ffn = OffloadedFFNRuntime._ffn_segments

    def first_half(self, layer, h, ids):
        y = ffn(self, layer, h, ids)
        return y.at[y.shape[0] // 2:].set(0.0)
    mp.setattr(OffloadedFFNRuntime, "_ffn_segments", first_half)


@pytest.mark.parametrize("fault", [altered_token, second_best_token,
                                   state_unchanged, half_batch])
def test_decode_fault_is_not_correct(root, fault, monkeypatch):
    fault(monkeypatch)
    assert _correct(root, "tiny.decode") is False


def test_prefill_altered_token_is_not_correct(root, monkeypatch):
    altered_token(monkeypatch)
    assert _correct(root, "tiny.prefill") is False


def test_prefill_second_best_token_is_not_correct(root, monkeypatch):
    second_best_token(monkeypatch)
    assert _correct(root, "tiny.prefill") is False


def test_unplanted_run_is_correct(root):
    assert _correct(root, "tiny.decode") is True
