"""Plain reference of a decoder with grouped-query attention, RMSNorm and a
gated SiLU FFN, for the CPU tests of a second configuration's shape.

Pre-RMSNorm decoder layers (no bias, epsilon 1e-6): RMSNorm, causal
self-attention with `n_kv_heads` key/value heads, each shared by
`n_heads / n_kv_heads` query heads, rotary positions (rotate-half form, base
`rope_theta`), residual; RMSNorm, FFN `(silu(x W_up) * (x W_gate)) W_down`,
residual; a final RMSNorm and the output projection tied to the token
embedding. Plain Gaussian weights from the seed; no activation share is
stated, so `forward_logits` returns none.

Written from that description in plain `jax.numpy`; nothing of the program is
imported, and of the benchmark only its seed key. Laid out as the program
takes the weights: `embed` (no `lm_head`: tied), `final_norm` and one stacked
`stack/sub_0` group.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, ClassVar, Dict

import jax
import jax.numpy as jnp

from bench.weights import seed_key

RMS_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class Dims:
    n_layers: int
    d_model: int
    d_ff: int
    n_heads: int
    n_kv_heads: int
    vocab: int
    rope_theta: float
    embedding_std: float
    n_mats: ClassVar[int] = 3          # W_up, W_gate and W_down per neuron

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @classmethod
    def from_config(cls, c: Dict[str, Any]) -> "Dims":
        return cls(n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   d_ff=c["intermediate_size"],
                   n_heads=c["num_attention_heads"],
                   n_kv_heads=c["num_key_value_heads"],
                   vocab=c["vocab_size"], rope_theta=c["rope_theta"],
                   embedding_std=c["assumed"]["embedding_std"])


@functools.partial(jax.jit, static_argnames=("dims",))
def _init(key, dims: Dims):
    L, d, f, V = dims.n_layers, dims.d_model, dims.d_ff, dims.vocab
    q, kv = dims.n_heads * dims.head_dim, dims.n_kv_heads * dims.head_dim
    ks = jax.random.split(key, 8)
    nrm = jax.random.normal
    scale = lambda *lead: {"scale": jnp.ones(lead + (d,), jnp.float32)}
    return {
        "embed": {"embedding": nrm(ks[0], (V, d)) * dims.embedding_std},
        "stack": {"sub_0": {
            "norm1": scale(L),
            "mixer": {"wq": nrm(ks[1], (L, d, q)) * d ** -0.5,
                      "wk": nrm(ks[2], (L, d, kv)) * d ** -0.5,
                      "wv": nrm(ks[3], (L, d, kv)) * d ** -0.5,
                      "wo": nrm(ks[4], (L, q, d)) * q ** -0.5},
            "norm2": scale(L),
            "ffn": {"w_up": nrm(ks[5], (L, d, f)) * d ** -0.5,
                    "w_down": nrm(ks[6], (L, f, d)) * f ** -0.5,
                    "w_gate": nrm(ks[7], (L, d, f)) * d ** -0.5}}},
        "final_norm": scale(),
    }


def make_params(dims: Dims, cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """Gaussian weights of `seed`, float32: embedding at the configuration's
    `embedding_std`, every other matrix at 1/sqrt(fan-in), norms at 1."""
    return _init(seed_key(seed), dims)


def rms_norm(p, x):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.square(xf).mean(-1, keepdims=True) + RMS_EPS)
    return (y * p["scale"]).astype(x.dtype)


def rotary(x, positions, theta):
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, :, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           -1).astype(x.dtype)


def attention(p, x, dims: Dims, precision):
    B, T, _ = x.shape
    H, KV, hd = dims.n_heads, dims.n_kv_heads, dims.head_dim
    mm = functools.partial(jnp.matmul, precision=precision)
    pos = jnp.broadcast_to(jnp.arange(T), (B, T))
    q = rotary(mm(x, p["wq"].astype(x.dtype)).reshape(B, T, H, hd), pos,
               dims.rope_theta)
    k = rotary(mm(x, p["wk"].astype(x.dtype)).reshape(B, T, KV, hd), pos,
               dims.rope_theta)
    v = mm(x, p["wv"].astype(x.dtype)).reshape(B, T, KV, hd)
    # query head h reads key/value head h // (H / KV)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bqhe,bkhe->bhqk", q, k, precision=precision)
    s = s.astype(jnp.float32) * hd ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    o = jnp.einsum("bhqk,bkhe->bqhe", w, v, precision=precision)
    return mm(o.reshape(B, T, H * hd), p["wo"].astype(x.dtype))


def layer(p, h, dims: Dims, precision):
    mm = functools.partial(jnp.matmul, precision=precision)
    h = h + attention(p["mixer"], rms_norm(p["norm1"], h), dims, precision)
    x = rms_norm(p["norm2"], h)
    f = p["ffn"]
    act = jax.nn.silu(mm(x, f["w_up"].astype(x.dtype))) \
        * mm(x, f["w_gate"].astype(x.dtype))
    return h + mm(act, f["w_down"].astype(x.dtype))


@functools.partial(jax.jit, static_argnames=("dims", "precision", "dtype"))
def _layer_step(p, h, dims, precision, dtype):
    p = jax.tree_util.tree_map(lambda a: a.astype(dtype), p)
    return layer(p, h.astype(dtype), dims, precision)


@functools.partial(jax.jit, static_argnames=("precision", "dtype"))
def _head(final_norm, emb, h, positions, precision, dtype):
    h = jnp.take_along_axis(h, positions[:, :, None], axis=1)
    h = rms_norm(jax.tree_util.tree_map(lambda a: a.astype(dtype), final_norm),
                 h)
    return jnp.matmul(h, emb.astype(dtype).T,
                      precision=precision).astype(jnp.float32)


# as in the OPT reference: the stated arithmetic, full float32, and the
# control one step below
PRECISIONS = {"float32": (jnp.float32, "highest"),
              "float32_default": (jnp.float32, "default"),
              "bfloat16": (jnp.bfloat16, "default")}


def forward_logits(params, blocks, dims: Dims, precision: str = "float32"):
    """For each block `(tokens [rows, T], positions [rows, n])`: the logits
    [rows, n, V] at those positions, and no activation share (None). One
    layer at a time over every block."""
    dtype, prec = PRECISIONS[precision]
    emb = params["embed"]["embedding"]
    hs = [emb.astype(dtype)[jnp.asarray(t)] for t, _ in blocks]
    stack = params["stack"]["sub_0"]
    for i in range(dims.n_layers):
        p = jax.tree_util.tree_map(lambda a: a[i], stack)
        hs = [_layer_step(p, h, dims, prec, dtype) for h in hs]
    return [(_head(params["final_norm"], emb, h, jnp.asarray(pos), prec, dtype),
             None) for h, (_, pos) in zip(hs, blocks)]
