"""Sharding rules: PartitionSpec per parameter/cache leaf, by pytree path.

Conventions (MaxText-style logical axes, resolved per-leaf with divisibility
checks):
  * "model" axis  — tensor parallel: FFN hidden (d_ff), attention heads,
    vocab, MoE experts, SSM inner dim.
  * "data" axis   — batch parallel + FSDP: the d_model (or other non-TP) dim
    of each weight is sharded over data as ZeRO-style FSDP; optimizer moments
    inherit the same specs (ZeRO-1 comes for free).
  * "pod" axis    — composes with "data" for batch/FSDP sharding across pods.

A candidate dim is only sharded when its size divides the axis size; otherwise
the next candidate is tried, else the dim stays replicated. Leading stacked
scan dims (layer groups) are never sharded.
"""
from __future__ import annotations

import re
from typing import Any, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


def make_mesh(axis_sizes: Sequence[int], axis_names: Sequence[str]) -> Mesh:
    """jax.make_mesh with Auto axis types: shardings stay compiler-propagated
    hints, which the PartitionSpec rules below are written for."""
    names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_sizes), names,
                         axis_types=(AxisType.Auto,) * len(names))


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def batch_spec(mesh: Mesh, batch_size: int, ndim: int) -> P:
    """Shard the leading batch dim over (pod, data) when divisible."""
    axes = dp_axes(mesh)
    total = int(np.prod([mesh.shape[a] for a in axes]))
    first = axes if batch_size % total == 0 else (
        ("data",) if batch_size % mesh.shape["data"] == 0 else None)
    return P(first, *([None] * (ndim - 1)))


# rule table: (path regex, [(axis_kind, candidate dims from the END)...])
# dims are negative indices; first divisible candidate wins.
_RULES: List[Tuple[str, List[Tuple[str, Sequence[int]]]]] = [
    (r"embed/embedding$",        [("model", (-2,)), ("data", (-1,))]),
    (r"embed/lm_head$",          [("model", (-1,)), ("data", (-2,))]),
    (r"projector/w[12]$",        [("model", (-1,)), ("data", (-2,))]),
    (r"frontend_proj$",          [("model", (-1,)), ("data", (-2,))]),
    # attention
    (r"(mixer|attn|self_attn|cross_attn)/w[qkv]$", [("model", (-1,)), ("data", (-2,))]),
    (r"(mixer|attn|self_attn|cross_attn)/wo$",     [("model", (-2,)), ("data", (-1,))]),
    (r"(mixer|attn|self_attn|cross_attn)/b[qkv]$", [("model", (-1,))]),
    # dense FFN
    (r"ffn/w_(up|gate)$",        [("model", (-1,)), ("data", (-2,))]),
    (r"ffn/w_down$",             [("model", (-2,)), ("data", (-1,))]),
    # MoE: experts first, then expert-ffn dim
    (r"ffn/router$",             [("data", (-2,))]),
    (r"ffn/w_(up|gate)$",        [("model", (-1,)), ("data", (-2,))]),   # covered above
    # mamba
    (r"mixer/in_proj$",          [("model", (-1,)), ("data", (-2,))]),
    (r"mixer/conv_w$",           [("model", (-1,))]),
    (r"mixer/conv_b$",           [("model", (-1,))]),
    (r"mixer/x_proj$",           [("model", (-2,))]),
    (r"mixer/dt_proj$",          [("model", (-1,))]),
    (r"mixer/dt_bias$",          [("model", (-1,))]),
    (r"mixer/A_log$",            [("model", (-2,))]),
    (r"mixer/D$",                [("model", (-1,))]),
    (r"mixer/out_proj$",         [("model", (-2,)), ("data", (-1,))]),
    # xLSTM
    (r"mixer/w[qkvo]$|mixer/w_o$", [("model", (-1,)), ("data", (-2,))]),
    (r"mixer/w_[if]$",           [("data", (-2,))]),
    (r"mixer/(w_z|w_i|w_f)$",    [("data", (-2,))]),
    (r"mixer/r_[zifo]$",         [("model", (-3,))]),
    (r"mixer/b_[zifo]$",         []),
]

# MoE expert tensors get a dedicated rule applied before the generic ffn ones.
_MOE_RULES: List[Tuple[str, List[Tuple[str, Sequence[int]]]]] = [
    (r"ffn/w_(up|gate)$", [("model", (-3, -1)), ("data", (-1, -2))]),   # [E, d, f]
    (r"ffn/w_down$",      [("model", (-3, -2)), ("data", (-2, -1))]),   # [E, f, d]
]


def _leaf_path_str(path) -> str:
    return "/".join(
        str(getattr(p, "key", getattr(p, "idx", getattr(p, "name", p)))) for p in path)


def _spec_for(path_str: str, shape: Tuple[int, ...], mesh: Mesh,
              is_moe_expert: bool) -> P:
    ndim = len(shape)
    if ndim == 0:
        return P()
    assignment: dict[int, str] = {}

    def try_assign(axis_name: str, candidates: Sequence[int]) -> None:
        if axis_name not in mesh.axis_names:
            return
        size = mesh.shape[axis_name]
        for c in candidates:
            dim = ndim + c if c < 0 else c
            if dim < 0 or dim >= ndim or dim in assignment:
                continue
            if shape[dim] % size == 0 and shape[dim] >= size:
                assignment[dim] = axis_name
                return

    rules = _MOE_RULES + _RULES if is_moe_expert else _RULES
    matched = False
    for pattern, axes in rules:
        if re.search(pattern, path_str):
            for axis_name, candidates in axes:
                try_assign(axis_name, candidates)
            matched = True
            break
    if not matched and ndim >= 2:
        try_assign("model", (-1, -2))
        try_assign("data", (-2, -1))
    spec = [assignment.get(d) for d in range(ndim)]
    return P(*spec)


def param_specs(params_shape: Any, mesh: Mesh,
                replicate_below: int = 0) -> Any:
    """PartitionSpec pytree matching an eval_shape'd params/opt-state tree.

    replicate_below: leaves with fewer elements are fully replicated — at
    small model scale per-layer TP all-reduces cost more than the redundant
    compute they save (§Perf xlstm finding).
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_shape)
    specs = []
    for path, leaf in flat:
        ps = _leaf_path_str(path)
        if replicate_below and int(np.prod(leaf.shape)) < replicate_below:
            specs.append(P(*([None] * len(leaf.shape))))
            continue
        is_moe = bool(re.search(r"ffn/(w_(up|gate|down))$", ps)) and len(leaf.shape) >= 3
        specs.append(_spec_for(ps, tuple(leaf.shape), mesh, is_moe))
    return jax.tree_util.tree_unflatten(treedef, specs)


def cache_specs(cache_shape: Any, mesh: Mesh, batch_size: int,
                shard_seq: bool = False, no_model: bool = False) -> Any:
    """Decode-cache sharding: batch over data axes; KV-heads/inner over model.

    Cache leaves (after the stacked layer-group dim) are:
      KVCache k/v [G, B, S, KV, hd]; SWACache pos [G, B, W];
      Mamba conv [G, B, dc-1, di] / ssm [G, B, di, N];
      mLSTM C [G, B, H, hd, hd], n [G, B, H, hd], m [G, B, H]; sLSTM [G, B, H, hd].
    """
    axes = dp_axes(mesh)
    total = int(np.prod([mesh.shape[a] for a in axes]))
    b_axes: Optional[Tuple[str, ...]] = axes if batch_size % total == 0 else (
        ("data",) if batch_size % mesh.shape["data"] == 0 else None)
    model_size = mesh.shape["model"]

    def spec(path, leaf) -> P:
        shape = leaf.shape
        ndim = len(shape)
        path_str = _leaf_path_str(path)
        # find batch dim: dim 1 for stacked caches ([G, B, ...]); dim 0 for
        # unstacked (encdec DecoderCache mem_k: [L, B, F, KV, hd] also stacked)
        out = [None] * ndim
        bdim = 1 if ndim >= 2 else 0
        if ndim >= 2 and shape[bdim] == batch_size and b_axes:
            out[bdim] = b_axes
        if no_model:        # replicated-compute variant (§Perf C3): batch only
            return P(*out)
        leaf_name = path_str.split("/")[-1]
        is_kv = leaf_name in ("k", "v") and ndim == 5
        is_scale = leaf_name.endswith("_scale") and ndim == 4   # int8 KV scales
        if is_scale:
            if shard_seq and shape[2] % model_size == 0:
                out[2] = "model"
            elif shape[3] % model_size == 0:
                out[3] = "model"
            return P(*out)
        if shard_seq and is_kv and shape[2] % model_size == 0:
            # §Perf variant: shard the KV SEQUENCE dim — attention reduces over
            # it, so SPMD emits small softmax-stat all-reduces instead of
            # resharding the whole cache (distributed flash-decode semantics).
            out[2] = "model"
            return P(*out)
        if ndim <= 3:                      # small bookkeeping leaves: batch only
            return P(*out)
        # model axis on a heads-like dim when divisible (prefer KV over hd)
        for d in ([ndim - 2, ndim - 1] if ndim >= 4 else [ndim - 1]):
            if d <= bdim:
                continue
            if is_kv and d == 2:           # never the sequence dim here
                continue
            if shape[d] % model_size == 0 and shape[d] >= model_size:
                out[d] = "model"
                break
        return P(*out)

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_shape)
    return jax.tree_util.tree_unflatten(treedef, [spec(p, l) for p, l in flat])


def named(tree_specs: Any, mesh: Mesh) -> Any:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tree_specs,
        is_leaf=lambda x: isinstance(x, P))
