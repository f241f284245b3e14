"""Peaks of each chip and the work (operations and bytes) each measured
computation needs.

The work is counted from what the algorithm needs, whatever implements it, so
a later change to a kernel cannot move the yardstick: the sparse FFN's work is
the activated union of neurons (segments and padding are implementation
waste), paged decode's is the K/V of every active slot's filled positions.
The sparse-FFN terms follow `sparse_ffn_segment_terms` in the repository's
`benchmarks/roofline.py`, with the covered span replaced by the union.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, Sequence


@dataclasses.dataclass(frozen=True)
class Peak:
    flops: float          # dense bf16 FLOP/s of one chip
    hbm_bytes_s: float    # HBM bytes/s of one chip
    hbm_bytes: float      # HBM capacity of one chip
    source: str


V5E = Peak(197e12, 819e9, 16e9,
           "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, 16 GB "
           "HBM at 819 GB/s per chip")

# Keyed by `jax.Device.device_kind`. A kind that is missing is an error: a
# share of an unknown peak would be a guess.
PEAKS = {"TPU v5 lite": V5E, "TPU v5e": V5E}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak is known for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def min_seconds(self, peak: Peak) -> float:
        """The least time the chip could take: the larger of the compute
        bound and the memory bound."""
        return max(self.flops / peak.flops, self.bytes / peak.hbm_bytes_s)

    def bound(self, peak: Peak) -> str:
        return ("compute" if self.flops / peak.flops
                >= self.bytes / peak.hbm_bytes_s else "memory")


def total(works: Iterable[Work]) -> Work:
    out = Work(0.0, 0.0)
    for w in works:
        out = out + w
    return out


def sparse_ffn_work(rows: int, union: int, n_mats: int, d_model: int,
                    weight_bytes: int = 4, act_bytes: int = 4) -> Work:
    """One sparse FFN call: `rows` activation rows through the `union`
    activated neurons, each neuron `n_mats` rows of `d_model` weights (up and
    down, plus gate for gated FFNs). Bytes are the union's weights, the
    activations in and the output out."""
    flops = 2.0 * rows * union * n_mats * d_model
    nbytes = (union * n_mats * d_model * weight_bytes
              + 2 * rows * d_model * act_bytes)
    return Work(flops, float(nbytes))


def paged_decode_work(positions: Sequence[int], n_heads: int, n_kv_heads: int,
                      head_dim: int, kv_bytes: int = 4,
                      act_bytes: int = 4) -> Work:
    """One paged-decode attention call over the active slots, where slot i
    attends to `positions[i] + 1` cached positions (its filled ones,
    the new token's included). Bytes are the K and V of those positions,
    plus each slot's query in and output out; FLOPs are q·K and p·V."""
    ctx = sum(int(p) + 1 for p in positions)
    flops = 4.0 * ctx * n_heads * head_dim
    nbytes = (2 * ctx * n_kv_heads * head_dim * kv_bytes
              + 2 * len(positions) * n_heads * head_dim * act_bytes)
    return Work(flops, float(nbytes))


def decoder_layer_weights(d_model: int, d_ff: int, n_heads: int,
                          n_kv_heads: int, n_mats: int) -> int:
    """Weights of one decoder layer's attention and dense FFN."""
    hd = d_model // n_heads
    return (d_model * hd * (n_heads + 2 * n_kv_heads) + n_heads * hd * d_model
            + n_mats * d_model * d_ff)


def decode_token_flops(position: int, *, n_layers: int, d_model: int,
                       d_ff: int, n_heads: int, n_kv_heads: int, vocab: int,
                       n_mats: int) -> float:
    """Dense-equivalent FLOPs of one decode token at `position`: every
    weight once (dense FFN, as if no neuron were skipped), attention over
    `position + 1` keys, and the unembedding."""
    w = decoder_layer_weights(d_model, d_ff, n_heads, n_kv_heads, n_mats)
    attn = 4.0 * (position + 1) * d_model
    return n_layers * (2.0 * w + attn) + 2.0 * d_model * vocab


def prefill_request_flops(prompt_len: int, *, n_layers: int, d_model: int,
                          d_ff: int, n_heads: int, n_kv_heads: int,
                          vocab: int, n_mats: int) -> float:
    """Dense-equivalent FLOPs of one prompt's prefill: every weight once
    per prompt token, causal attention (T(T+1)/2 query-key pairs, each with
    q·k and p·v), and the unembedding of the last position, which gives the
    first token."""
    T = prompt_len
    w = decoder_layer_weights(d_model, d_ff, n_heads, n_kv_heads, n_mats)
    pairs = T * (T + 1) / 2
    return n_layers * (2.0 * w * T + 4.0 * pairs * d_model) \
        + 2.0 * d_model * vocab
