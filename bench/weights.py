"""Seeded weights, made on the device in one jitted call, with FFN activation
statistics like a trained ReLU model's.

For references of pre-LayerNorm decoders with a two-matrix ReLU FFN
(`relu(LayerNorm(h) W_up) W_down`), laid out as one stacked `stack/sub_0`
group: the OPT family. The reference passes in what of it this library runs
(its initialiser, attention, LayerNorm and epsilon); a model of another shape
makes its weights in its own reference module.

Plain Gaussian weights make every ReLU pre-activation symmetric about zero:
each neuron fires for about half the tokens, independently of every other, so
the activated union of a batch is nearly all of `d_ff` and co-activation
placement has nothing to link. Trained OPT models fire a few percent of their
neurons per token, in correlated groups. This module shapes each layer's FFN
so that it does:

  * neurons fall into groups of `group_size` (scattered over the logical
    order by a seeded permutation); neuron j's input direction is
    sqrt(rho) v_g + sqrt(1 - rho) r_j, a shared direction of its group plus
    its own, centred and of unit norm;
  * a negative offset per neuron comes in through the pre-FFN LayerNorm's
    bias: the bias is `input_bias` on every feature, and each `w_up` column
    carries a constant delta_j, so the offset is input_bias * d * delta_j.
    The normalised input sums to zero over its features, so the constant
    column adds nothing else, and neuron j fires exactly when its direction's
    projection passes its threshold theta_j;
  * theta_j is set, layer by layer, so that `share` of the neuron-token
    pairs of a seeded calibration batch, run through the layers below, fire
    (see `_thresholds`).

The statistics are synthetic: no trained model's co-activation data is in the
repository. The reference takes the same weights.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A key that keeps every bit of a seed wider than 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**32), seed // 2**32)


def _ffn_directions(key, d: int, f: int, group_size: int, rho: float):
    """[d, f] unit, zero-mean columns: group direction plus own direction."""
    kg, kr, kp = jax.random.split(key, 3)
    n_groups = f // group_size
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    v = unit(jax.random.normal(kg, (n_groups, d)))
    r = unit(jax.random.normal(kr, (f, d)))
    group = jax.random.permutation(kp, f) // group_size
    e = jnp.sqrt(rho) * v[group] + jnp.sqrt(1.0 - rho) * r
    e = e - e.mean(axis=-1, keepdims=True)
    return unit(e).T


def _thresholds(pre, share):
    """Per-neuron thresholds that `share` of the tokens pass: each neuron's
    mean plus its standard deviation times one pooled quantile of the
    standardised pre-activations. Means and deviations over a calibration
    batch are far steadier than each neuron's own tail quantile, whose noise
    would push the share above its target."""
    mu = pre.mean(axis=0)
    sd = pre.std(axis=0)
    u = (pre - mu) / sd

    def bisect(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        over = jnp.mean(u > mid) > share
        return jnp.where(over, mid, lo), jnp.where(over, hi, mid)

    lo, hi = jax.lax.fori_loop(0, 40, bisect, (jnp.float32(-10.0),
                                               jnp.float32(10.0)))
    return mu + sd * 0.5 * (lo + hi)


def _shape_ffn(attention, layer_norm, eps, params, key, dims,
               stats: Dict[str, Any]):
    """Replace each layer's `w_up` and pre-FFN LayerNorm bias (see module
    docstring), calibrating thresholds on a seeded token batch."""
    stack = params["stack"]["sub_0"]
    L, d, f = dims.n_layers, dims.d_model, dims.d_ff
    n_b, n_t = stats["calibration_batch"]
    k_tok, k_dir, k_emb = jax.random.split(key, 3)
    emb = jax.random.normal(k_emb, (dims.vocab, d)) * stats["embedding_std"]
    params = dict(params, embed=dict(params["embed"], embedding=emb))
    tokens = jax.random.randint(k_tok, (n_b, n_t), 0, dims.vocab)
    h = emb[tokens]
    n_tok = n_b * n_t
    c = float(stats["input_bias"])

    def body(h, xs):
        p, lkey = xs
        e = _ffn_directions(lkey, d, f, stats["group_size"], stats["rho"])
        h = h + attention(p["mixer"], layer_norm(p["norm1"], h), dims,
                          "default")
        xf = h - h.mean(-1, keepdims=True)
        n = xf * jax.lax.rsqrt(jnp.square(xf).mean(-1, keepdims=True) + eps)
        pre = n.reshape(n_tok, d) @ e                          # [tokens, f]
        theta = _thresholds(pre, stats["share"])
        w_up = e - (theta / (c * d))[None, :]
        x = n + c
        h = h + jax.nn.relu(x @ w_up) @ p["ffn"]["w_down"]
        return h, w_up

    layer_keys = jax.random.split(k_dir, L)
    xs = {"mixer": stack["mixer"], "norm1": stack["norm1"],
          "ffn": {"w_down": stack["ffn"]["w_down"]}}
    _, w_up = jax.lax.scan(body, h, (xs, layer_keys))
    stack = dict(stack)
    stack["ffn"] = dict(stack["ffn"], w_up=w_up)
    stack["norm2"] = {"scale": jnp.ones((L, d), jnp.float32),
                      "bias": jnp.full((L, d), c, jnp.float32)}
    return dict(params, stack={"sub_0": stack})


@functools.partial(jax.jit, static_argnames=(
    "init_params", "attention", "layer_norm", "eps", "dims", "stats_items"))
def _make(key, init_params, attention, layer_norm, eps, dims, stats_items):
    stats = dict(stats_items)
    k_init, k_ffn = jax.random.split(key)
    return _shape_ffn(attention, layer_norm, eps, init_params(dims, k_init),
                      k_ffn, dims, stats)


STATS_KEYS = ("share", "group_size", "rho", "input_bias", "embedding_std",
              "calibration_batch")


def make_params(init_params, attention, layer_norm, eps: float, dims,
                stats: Dict[str, Any], seed: int):
    """The served weights of `seed`, float32, on the default device:
    `init_params(dims, key)` lays them out, then each layer's FFN is shaped
    on a calibration batch run through `attention(p, x, dims, precision)`
    after `layer_norm(p, x)`; `eps` is the LayerNorm's epsilon."""
    items = tuple((k, tuple(stats[k]) if isinstance(stats[k], list)
                   else stats[k]) for k in STATS_KEYS)
    return _make(seed_key(seed), init_params, attention, layer_norm, float(eps),
                 dims, items)
