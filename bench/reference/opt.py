"""Plain reference of the OPT-shaped decoder the program serves.

Pre-LayerNorm decoder layers: LayerNorm, causal multi-head self-attention with
rotary position embeddings (rotate-half form, base `rope_theta`), residual;
LayerNorm, ReLU FFN (`relu(x W_up) W_down`, no biases), residual; a final
LayerNorm and an untied output projection. Departures from published OPT,
which the program makes and this reference follows: rotary positions instead
of learned ones, no attention or FFN biases, untied embeddings.

Written from that description in plain `jax.numpy`; nothing of the program is
imported. The harness calls three things of a reference module, and nothing
else: `Dims.from_config(cfg)` (the sizes, read from the configuration's
published keys), `make_params(dims, cfg, seed)` (the served weights) and
`forward_logits(params, blocks, dims, precision)`, which runs one layer at a
time on blocks of sequences, so it fits beside nothing else on the chip.

`init_params` lays the weights out as the program takes them: a dict with
`embed`, `final_norm` and one stacked `stack/sub_0` group whose leaves carry
the layer axis first. `make_params` then shapes each FFN's activation
statistics (`bench/weights.py`).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, ClassVar, Dict

import jax
import jax.numpy as jnp

from bench import weights

LN_EPS = 1e-5


@dataclasses.dataclass(frozen=True)
class Dims:
    n_layers: int
    d_model: int
    d_ff: int
    n_heads: int
    vocab: int
    rope_theta: float
    n_mats: ClassVar[int] = 2          # W_up and W_down per neuron

    @property
    def n_kv_heads(self) -> int:
        return self.n_heads            # multi-head attention

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @classmethod
    def from_config(cls, c: Dict[str, Any]) -> "Dims":
        return cls(n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   d_ff=c["ffn_dim"], n_heads=c["num_attention_heads"],
                   vocab=c["vocab_size"], rope_theta=c["rope_theta"])


def init_params(dims: Dims, key: jax.Array) -> Dict[str, Any]:
    """Gaussian weights at the program's scales (embedding 0.02, every other
    matrix 1/sqrt(fan-in)), LayerNorms at scale 1 and bias 0."""
    L, d, f, V = dims.n_layers, dims.d_model, dims.d_ff, dims.vocab
    ks = jax.random.split(key, 8)
    nrm = jax.random.normal
    ln = lambda *lead: {"scale": jnp.ones(lead + (d,), jnp.float32),
                        "bias": jnp.zeros(lead + (d,), jnp.float32)}
    return {
        "embed": {"embedding": nrm(ks[0], (V, d)) * 0.02,
                  "lm_head": nrm(ks[1], (d, V)) * d ** -0.5},
        "stack": {"sub_0": {
            "norm1": ln(L),
            "mixer": {"wq": nrm(ks[2], (L, d, d)) * d ** -0.5,
                      "wk": nrm(ks[3], (L, d, d)) * d ** -0.5,
                      "wv": nrm(ks[4], (L, d, d)) * d ** -0.5,
                      "wo": nrm(ks[5], (L, d, d)) * d ** -0.5},
            "norm2": ln(L),
            "ffn": {"w_up": nrm(ks[6], (L, d, f)) * d ** -0.5,
                    "w_down": nrm(ks[7], (L, f, d)) * f ** -0.5}}},
        "final_norm": ln(),
    }


def make_params(dims: Dims, cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The served weights of `seed`, float32, on the default device: laid out
    by `init_params`, each FFN shaped to the configuration's stated
    activation statistics (`assumed.activations`)."""
    return weights.make_params(init_params, attention, layer_norm, LN_EPS, dims,
                               cfg["assumed"]["activations"], seed)


def layer_norm(p, x):
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = jnp.square(xf - mu).mean(-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + LN_EPS)
    return (y * p["scale"] + p["bias"]).astype(x.dtype)


def rotary(x, positions, theta):
    """x [B, T, H, hd]; rotate the two halves of each head by position."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, :, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           -1).astype(x.dtype)


def attention(p, x, dims: Dims, precision):
    B, T, d = x.shape
    H, hd = dims.n_heads, dims.head_dim
    mm = functools.partial(jnp.matmul, precision=precision)
    pos = jnp.broadcast_to(jnp.arange(T), (B, T))
    q = rotary(mm(x, p["wq"].astype(x.dtype)).reshape(B, T, H, hd), pos,
               dims.rope_theta)
    k = rotary(mm(x, p["wk"].astype(x.dtype)).reshape(B, T, H, hd), pos,
               dims.rope_theta)
    v = mm(x, p["wv"].astype(x.dtype)).reshape(B, T, H, hd)
    s = jnp.einsum("bqhe,bkhe->bhqk", q, k, precision=precision)
    s = s.astype(jnp.float32) * hd ** -0.5
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    o = jnp.einsum("bhqk,bkhe->bqhe", w, v, precision=precision)
    return mm(o.reshape(B, T, d), p["wo"].astype(x.dtype))


def layer(p, h, dims: Dims, precision):
    """One decoder layer; also returns the FFN pre-activations' sign, for the
    activation-share reading."""
    h = h + attention(p["mixer"], layer_norm(p["norm1"], h), dims, precision)
    x = layer_norm(p["norm2"], h)
    pre = jnp.matmul(x, p["ffn"]["w_up"].astype(x.dtype), precision=precision)
    y = jnp.matmul(jax.nn.relu(pre), p["ffn"]["w_down"].astype(x.dtype),
                   precision=precision)
    return h + y, pre > 0


@functools.partial(jax.jit, static_argnames=("dims", "precision", "dtype"))
def _layer_step(p, h, dims, precision, dtype):
    p = jax.tree_util.tree_map(lambda a: a.astype(dtype), p)
    h, fired = layer(p, h.astype(dtype), dims, precision)
    return h, fired.mean(axis=-1)


@functools.partial(jax.jit, static_argnames=("dtype",))
def _embed(emb, tokens, dtype):
    return emb.astype(dtype)[tokens]


@functools.partial(jax.jit, static_argnames=("precision", "dtype"))
def _head(final_norm, lm_head, h, positions, precision, dtype):
    h = jnp.take_along_axis(h, positions[:, :, None], axis=1)
    h = layer_norm(jax.tree_util.tree_map(lambda a: a.astype(dtype),
                                          final_norm), h)
    return jnp.matmul(h, lm_head.astype(dtype),
                      precision=precision).astype(jnp.float32)


# "float32_default" is the configuration's stated arithmetic: float32
# weights and activations, each product in one bfloat16 pass (the TPU's
# default for float32). "float32" computes every product at full float32
# ("highest"). The control is one step below the stated arithmetic:
# bfloat16 weights and activations, the products in one bfloat16 pass.
PRECISIONS = {"float32": (jnp.float32, "highest"),
              "float32_default": (jnp.float32, "default"),
              "bfloat16": (jnp.bfloat16, "default")}


def forward_logits(params, blocks, dims: Dims, precision: str = "float32"):
    """For each block `(tokens [rows, T], positions [rows, n])`: the logits
    [rows, n, V] at those positions, and the share of FFN neurons that fired
    per token [L, rows, T]. One layer at a time over every block, so only one
    layer's weights are sliced out and live beside the blocks' activations."""
    dtype, prec = PRECISIONS[precision]
    hs = [_embed(params["embed"]["embedding"], jnp.asarray(t), dtype)
          for t, _ in blocks]
    stack = params["stack"]["sub_0"]
    shares = [[] for _ in blocks]
    for i in range(dims.n_layers):
        p = jax.tree_util.tree_map(lambda a: a[i], stack)
        for b in range(len(blocks)):
            hs[b], share = _layer_step(p, hs[b], dims, prec, dtype)
            shares[b].append(share)
    return [(_head(params["final_norm"], params["embed"]["lm_head"], h,
                   jnp.asarray(pos), prec, dtype), jnp.stack(sh))
            for h, (_, pos), sh in zip(hs, blocks, shares)]
