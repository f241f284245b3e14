"""Decoder stack assembly: heterogeneous layers under a single lax.scan.

The per-layer mixer/FFN pattern (cfg.layer_kinds / cfg.ffn_kinds) is detected
to be periodic with period P; the stack is scanned over n_layers/P groups, each
group applying P sublayers unrolled. This keeps HLO size O(P), which is what
makes 88-layer configs compile quickly on one host and is standard MaxText
practice. Parameters and caches are stacked [G, ...] along the scan axis.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import moe as moe_lib
from repro.models import ssm
from repro.kernels import ops
from repro.obs import get_metrics, get_tracer
from repro.models.kvcache import (KVCache, PagedKVCache, PagedQuantKVCache,
                                  QuantKVCache, SWACache, attend_full_cache,
                                  attend_swa_cache,
                                  init_kv_cache, init_paged_kv_cache,
                                  init_paged_quant_kv_cache,
                                  init_quant_kv_cache, init_swa_cache,
                                  kv_write, kv_write_rows,
                                  paged_kv_write_rows,
                                  paged_quant_kv_write_rows, quant_kv_write,
                                  quant_kv_write_rows, swa_write)
from repro.models.layers import (apply_norm, attention_forward, ffn_forward,
                                 init_attention, init_ffn, init_ffn_predictor,
                                 init_norm, rope, sparse_ffn_decode)

Params = Dict[str, Any]


def stack_period(cfg: ModelConfig) -> int:
    kinds, ffns = cfg.layer_kinds(), cfg.ffn_kinds()
    L = cfg.n_layers
    for P in range(1, L + 1):
        if L % P:
            continue
        if all(kinds[i] == kinds[i % P] for i in range(L)) and \
           all(ffns[i] == ffns[i % P] for i in range(L)):
            return P
    return L


# -- init ---------------------------------------------------------------------

def _init_sublayer(key: jax.Array, cfg: ModelConfig, kind: str, ffn: str) -> Params:
    kmix, kffn = jax.random.split(key)
    p: Params = {"norm1": init_norm(cfg)}
    if kind == "attn":
        p["mixer"] = init_attention(kmix, cfg)
    elif kind == "mamba":
        p["mixer"] = ssm.init_mamba(kmix, cfg)
    elif kind == "mlstm":
        p["mixer"] = ssm.init_mlstm(kmix, cfg)
    elif kind == "slstm":
        p["mixer"] = ssm.init_slstm(kmix, cfg)
    else:
        raise ValueError(kind)
    if ffn == "dense":
        p["norm2"] = init_norm(cfg)
        p["ffn"] = init_ffn(kffn, cfg)
        if cfg.serve_sparse:
            p["ffn_pred"] = init_ffn_predictor(jax.random.fold_in(kffn, 7), cfg)
    elif ffn == "moe":
        p["norm2"] = init_norm(cfg)
        p["ffn"] = moe_lib.init_moe(kffn, cfg)
    return p


def init_stack(key: jax.Array, cfg: ModelConfig) -> Params:
    P = stack_period(cfg)
    G = cfg.n_layers // P
    kinds, ffns = cfg.layer_kinds(), cfg.ffn_kinds()
    stack: Params = {}
    for j in range(P):
        keys = jax.random.split(jax.random.fold_in(key, j), G)
        stack[f"sub_{j}"] = jax.vmap(
            lambda k: _init_sublayer(k, cfg, kinds[j], ffns[j]))(keys)
    return stack


# -- full-sequence forward ------------------------------------------------------

class StackOutput(NamedTuple):
    x: jnp.ndarray
    aux_loss: jnp.ndarray                     # scalar (MoE load balance)
    ffn_pre_act: Optional[jnp.ndarray]        # [L_dense, B, T, d_ff] if captured
    ffn_inputs: Optional[jnp.ndarray] = None  # [L_dense, B, T, d_model] if captured


def stack_forward(
    stack: Params,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    cfg: ModelConfig,
    window: int = 0,
    capture_activations: bool = False,
) -> StackOutput:
    P = stack_period(cfg)
    kinds, ffns = cfg.layer_kinds(), cfg.ffn_kinds()

    def group_fn(carry, group_params):
        h = carry
        aux_total = jnp.zeros((), jnp.float32)
        captures: List[jnp.ndarray] = []
        captures_h: List[jnp.ndarray] = []
        for j in range(P):
            sp = group_params[f"sub_{j}"]
            kind, ffn = kinds[j], ffns[j]
            normed = apply_norm(sp["norm1"], h, cfg)
            if kind == "attn":
                mix = attention_forward(sp["mixer"], normed, positions, cfg, window=window)
            elif kind == "mamba":
                mix = ssm.mamba_forward(sp["mixer"], normed, cfg)
            elif kind == "mlstm":
                mix = ssm.mlstm_forward(sp["mixer"], normed, cfg)
            else:
                mix = ssm.slstm_forward(sp["mixer"], normed, cfg)
            h = h + mix
            if ffn != "none":
                normed2 = apply_norm(sp["norm2"], h, cfg)
                if ffn == "dense":
                    y, pre = ffn_forward(sp["ffn"], normed2, cfg, capture=capture_activations)
                    if capture_activations:
                        captures.append(pre)
                        captures_h.append(normed2)
                else:
                    y, aux = moe_lib.moe_forward(sp["ffn"], normed2, cfg)
                    aux_total = aux_total + aux
                h = h + y
        cap = jnp.stack(captures) if captures else jnp.zeros((0,), h.dtype)
        cap_h = jnp.stack(captures_h) if captures_h else jnp.zeros((0,), h.dtype)
        return h, (aux_total, cap, cap_h)

    fn = jax.checkpoint(group_fn) if cfg.remat else group_fn
    x, (aux, caps, caps_h) = jax.lax.scan(fn, x, stack)
    aux_loss = aux.sum()
    pre_act = ffn_inputs = None
    if capture_activations and caps.size:
        # caps: [G, n_dense_per_period, B, T, d_ff] -> [L_dense, B, T, d_ff]
        pre_act = caps.reshape((-1,) + caps.shape[2:])
        # pre-FFN hidden states, same layer order — the lookahead predictor's
        # training input (layer k's hidden predicts layer k+1's mask)
        ffn_inputs = caps_h.reshape((-1,) + caps_h.shape[2:])
    return StackOutput(x=x, aux_loss=aux_loss, ffn_pre_act=pre_act,
                       ffn_inputs=ffn_inputs)


# -- caches ----------------------------------------------------------------------

def init_stack_cache(
    cfg: ModelConfig,
    batch: int,
    max_len: int,
    swa: bool = False,
    dtype=None,
) -> Params:
    """Cache pytree: per sublayer position, leaves stacked [G, ...]."""
    P = stack_period(cfg)
    G = cfg.n_layers // P
    kinds = cfg.layer_kinds()
    dtype = dtype or cfg.dtype()

    def stacked(make_one):
        one = make_one()
        return jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (G,) + a.shape).copy(), one)

    cache: Params = {}
    for j in range(P):
        kind = kinds[j]
        if kind == "attn":
            if swa:
                cache[f"sub_{j}"] = stacked(lambda: init_swa_cache(batch, cfg, dtype))
            elif cfg.kv_quant:
                cache[f"sub_{j}"] = stacked(lambda: init_quant_kv_cache(batch, max_len, cfg))
            else:
                cache[f"sub_{j}"] = stacked(lambda: init_kv_cache(batch, max_len, cfg, dtype))
        elif kind == "mamba":
            cache[f"sub_{j}"] = stacked(lambda: ssm.mamba_init_state(batch, cfg, dtype))
        elif kind == "mlstm":
            cache[f"sub_{j}"] = stacked(lambda: ssm.mlstm_init_state(batch, cfg, dtype))
        else:
            cache[f"sub_{j}"] = stacked(lambda: ssm.slstm_init_state(batch, cfg, dtype))
    return cache


def init_paged_stack_cache(
    cfg: ModelConfig,
    num_pages: int,
    page_size: int,
    dtype=None,
) -> Params:
    """Paged cache pytree: per attention sublayer, a page arena stacked
    [G, num_pages + 1, page_size, KV, hd] (the trailing null page absorbs
    inactive-slot writes). One set of `num_pages` logical pages serves every
    layer — a page table entry indexes all G x P arenas at once, vLLM-style —
    so allocator accounting stays per-request, not per-layer.

    Raises ValueError for stacks the paged layout cannot represent (SSM
    sublayers keep per-slot recurrent state, not positional KV) — no silent
    fallback to a contiguous cache."""
    if num_pages < 1 or page_size < 1:
        raise ValueError(f"paged cache needs num_pages >= 1 and page_size >= 1, "
                         f"got num_pages={num_pages} page_size={page_size}")
    kinds = cfg.layer_kinds()
    if any(k != "attn" for k in kinds):
        raise ValueError(
            f"paged KV cache covers attention-only stacks; config "
            f"{cfg.arch_id!r} has layer kinds {sorted(set(kinds))} (SSM "
            f"sublayers carry per-slot recurrent state, which pages cannot "
            f"represent)")
    P = stack_period(cfg)
    G = cfg.n_layers // P
    dtype = dtype or cfg.dtype()

    def stacked(make_one):
        one = make_one()
        return jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (G,) + a.shape).copy(), one)

    cache: Params = {}
    for j in range(P):
        if cfg.kv_quant:
            cache[f"sub_{j}"] = stacked(
                lambda: init_paged_quant_kv_cache(num_pages, page_size, cfg))
        else:
            cache[f"sub_{j}"] = stacked(
                lambda: init_paged_kv_cache(num_pages, page_size, cfg, dtype))
    return cache


# -- prefill ----------------------------------------------------------------------

def _attn_seq_with_cache(sp, normed, positions, cfg, cache, window):
    """Sequence attention that also fills the cache (prefill path).

    Long sequences route through flash attention exactly like
    attention_forward — the dense [T, S] score matrix at 32k would be
    hundreds of GiB (§Perf X7)."""
    from repro.models.layers import (FLASH_SEQ_THRESHOLD, _project_qkv,
                                     flash_gqa_attend,
                                     flash_gqa_attend_triangular, gqa_attend)
    q, k, v = _project_qkv(sp, normed, normed, cfg)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    if normed.shape[1] > FLASH_SEQ_THRESHOLD:
        if cfg.flash_triangular:
            out = flash_gqa_attend_triangular(q, k, v, positions, window=window,
                                              chunk=cfg.flash_q_chunk)
        else:
            out = flash_gqa_attend(q, k, v, positions, positions, causal=True,
                                   window=window, q_chunk=cfg.flash_q_chunk,
                                   k_chunk=cfg.flash_k_chunk)
    else:
        out = gqa_attend(q, k, v, positions, positions, causal=True, window=window)
    if isinstance(cache, SWACache):
        cache = swa_write(cache, k, v, positions)
    elif isinstance(cache, QuantKVCache):
        cache = quant_kv_write(cache, k, v, 0)
    else:
        cache = kv_write(cache, k, v, 0)
    return out @ sp["wo"], cache


def stack_prefill(
    stack: Params,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    cache: Params,
    cfg: ModelConfig,
    window: int = 0,
) -> Tuple[jnp.ndarray, Params]:
    P = stack_period(cfg)
    kinds, ffns = cfg.layer_kinds(), cfg.ffn_kinds()

    def group_fn(carry, inp):
        h = carry
        group_params, group_cache = inp
        new_cache: Params = {}
        for j in range(P):
            sp = group_params[f"sub_{j}"]
            cj = group_cache[f"sub_{j}"]
            kind, ffn = kinds[j], ffns[j]
            normed = apply_norm(sp["norm1"], h, cfg)
            if kind == "attn":
                mix, cj = _attn_seq_with_cache(sp["mixer"], normed, positions, cfg, cj, window)
            elif kind == "mamba":
                mix, cj = ssm.mamba_forward(sp["mixer"], normed, cfg, return_state=True)
            elif kind == "mlstm":
                mix, cj = ssm.mlstm_forward(sp["mixer"], normed, cfg, return_state=True)
            else:
                mix, cj = ssm.slstm_forward(sp["mixer"], normed, cfg, return_state=True)
            h = h + mix
            if ffn != "none":
                normed2 = apply_norm(sp["norm2"], h, cfg)
                if ffn == "dense":
                    y, _ = ffn_forward(sp["ffn"], normed2, cfg)
                else:
                    y, _ = moe_lib.moe_forward(sp["ffn"], normed2, cfg)
                h = h + y
            new_cache[f"sub_{j}"] = cj
        return h, new_cache

    x, new_cache = jax.lax.scan(group_fn, x, (stack, cache))
    return x, new_cache


# -- single-token decode -----------------------------------------------------------

def _decode_positions(position: jnp.ndarray, B: int) -> jnp.ndarray:
    """[B, 1] decode positions from either a shared scalar or a per-slot [B]
    vector (the continuous-batching server: every KV-cache slot sits at its
    own sequence position)."""
    pos = jnp.asarray(position).astype(jnp.int32)
    if pos.ndim == 1:
        return pos[:, None]
    return jnp.broadcast_to(pos, (B, 1))


def _is_paged(cj: Any) -> bool:
    return isinstance(cj, (PagedKVCache, PagedQuantKVCache))


def _paged_write(cj: Any):
    """The arena's KV write, by its type (float or int8 with scales)."""
    if isinstance(cj, PagedQuantKVCache):
        return paged_quant_kv_write_rows
    return paged_kv_write_rows


def _attn_pre(sp: Params, cj: Any, h: jnp.ndarray, pos_arr: jnp.ndarray,
              position: jnp.ndarray, page_tables: Optional[jnp.ndarray],
              cfg: ModelConfig, write) -> Tuple[jnp.ndarray, Any]:
    """Paged attention up to the kernel: norm1 -> QKV -> rope -> the KV
    `write` (`_paged_write(cj)`) through `page_tables` [B, max_pages].
    Returns (q [B, H, hd], new cache). Paged decode needs per-slot [B]
    positions (the paged layout exists for the continuous-batching
    server)."""
    from repro.models.layers import _project_qkv
    if page_tables is None:
        raise ValueError("paged KV cache decode needs page_tables")
    if jnp.asarray(position).ndim != 1:
        raise ValueError("paged KV cache decode needs per-slot [B] "
                         "positions (continuous batching)")
    normed = apply_norm(sp["norm1"], h, cfg)
    q, k, v = _project_qkv(sp["mixer"], normed, normed, cfg)
    q = rope(q, pos_arr, cfg.rope_theta)
    k = rope(k, pos_arr, cfg.rope_theta)
    return q[:, 0], write(cj, k, v, position, page_tables)


def _paged_attn(q: jnp.ndarray, cj: Any, page_tables: jnp.ndarray,
                cur_pos: jnp.ndarray) -> jnp.ndarray:
    """The paged-decode kernel over one layer's arena: [B, H, hd] fp32.
    `kernels/ops.paged_decode_attention` dispatches the XLA gather twin on
    CPU and the Pallas kernel elsewhere."""
    if isinstance(cj, PagedQuantKVCache):
        return ops.paged_decode_attention(q, cj.k, cj.v, page_tables, cur_pos,
                                          k_scale=cj.k_scale,
                                          v_scale=cj.v_scale)
    return ops.paged_decode_attention(q, cj.k, cj.v, page_tables, cur_pos)


def _attn_post(sp: Params, h: jnp.ndarray, out: jnp.ndarray,
               cfg: ModelConfig, ffn: str
               ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Paged attention after the kernel: fold the kernel's [B, H, hd] fp32 to
    the [B, 1, H*hd] residual layout at q's dtype -> wo -> residual ->
    norm2. Returns (h, normed2; None without an FFN)."""
    wo = sp["mixer"]["wo"]
    B, H, hd = out.shape
    mix = out.reshape(B, 1, H * hd).astype(jnp.result_type(h, wo))
    h = h + mix @ wo
    return h, _norm2(sp, h, cfg, ffn)


def _norm2(sp: Params, h: jnp.ndarray, cfg: ModelConfig, ffn: str
           ) -> Optional[jnp.ndarray]:
    return None if ffn == "none" else apply_norm(sp["norm2"], h, cfg)


def _sublayer_mix(sp: Params, cj: Any, h: jnp.ndarray, pos_arr: jnp.ndarray,
                  position: jnp.ndarray, page_tables: Optional[jnp.ndarray],
                  cfg: ModelConfig, kind: str, ffn: str, window: int
                  ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray], Any]:
    """One sublayer's mixer for a single decode token, with its residual and
    norm2: (h, normed2 [B,1,d] or None without an FFN, new cache).

    The scan path (stack_decode_step) calls it inline; the layerwise path
    (stack_decode_step_layerwise) runs it as one compiled call, or, for a
    paged arena, as its three pieces `_attn_pre` -> `_paged_attn` ->
    `_attn_post`, so both paths run identical math. `position` is a shared
    scalar or a per-slot [B] vector; the full-cache writes pick the matching
    (slice vs per-row scatter) variant."""
    if _is_paged(cj):
        q, cj = _attn_pre(sp, cj, h, pos_arr, position, page_tables, cfg,
                          _paged_write(cj))
        out = _paged_attn(q, cj, page_tables,
                          jnp.asarray(position).astype(jnp.int32))
        h, normed2 = _attn_post(sp, h, out, cfg, ffn)
        return h, normed2, cj
    per_row = jnp.asarray(position).ndim == 1
    normed = apply_norm(sp["norm1"], h, cfg)
    if kind == "attn":
        from repro.models.layers import _project_qkv
        q, k, v = _project_qkv(sp["mixer"], normed, normed, cfg)
        q = rope(q, pos_arr, cfg.rope_theta)
        k = rope(k, pos_arr, cfg.rope_theta)
        if isinstance(cj, SWACache):
            cj = swa_write(cj, k, v, pos_arr)
            mix = attend_swa_cache(q, cj, pos_arr, window or cfg.sliding_window)
        elif isinstance(cj, QuantKVCache):
            cj = (quant_kv_write_rows(cj, k, v, position) if per_row
                  else quant_kv_write(cj, k, v, position))
            mix = attend_full_cache(q, cj, pos_arr)
        else:
            cj = (kv_write_rows(cj, k, v, position) if per_row
                  else kv_write(cj, k, v, position))
            mix = attend_full_cache(q, cj, pos_arr)
        mix = mix @ sp["mixer"]["wo"]
    elif kind == "mamba":
        y, cj = ssm.mamba_decode_step(sp["mixer"], normed[:, 0], cj, cfg)
        mix = y[:, None]
    elif kind == "mlstm":
        y, cj = ssm.mlstm_decode_step(sp["mixer"], normed[:, 0], cj, cfg)
        mix = y[:, None]
    else:
        y, cj = ssm.slstm_decode_step(sp["mixer"], normed[:, 0], cj, cfg)
        mix = y[:, None]
    h = h + mix
    return h, _norm2(sp, h, cfg, ffn), cj


def _compiled(fn, *static: str):
    """`fn` as one jitted call with `static` arguments. Each trace counts in
    the `model.mixer_traces` counter, so a retrace inside a serving window
    (a changed shape, a Python scalar where an array belongs) shows."""
    @functools.wraps(fn)
    def traced(*args, **kwargs):
        get_metrics().counter("model.mixer_traces").inc()
        return fn(*args, **kwargs)
    return jax.jit(traced, static_argnames=static)


# The layerwise path's compiled mixer pieces, built once: what compiles
# follows the sublayer's kind and its cache's type and shapes. The paged
# kernel stays its own module between `_attn_pre` and `_attn_post`. The
# arena's write goes in static, looked up by the caller on each call, so a
# write replaced in this module compiles anew instead of hitting the cache.
_attn_pre_jit = _compiled(_attn_pre, "cfg", "write")
_attn_post_jit = _compiled(_attn_post, "cfg", "ffn")
_sublayer_mix_jit = _compiled(_sublayer_mix, "cfg", "kind", "ffn", "window")


def stack_decode_step(
    stack: Params,
    x: jnp.ndarray,            # [B, 1, d]
    position: jnp.ndarray,     # scalar int32 (shared) or [B] per-slot positions
    cache: Params,
    cfg: ModelConfig,
    window: int = 0,
    page_tables: Optional[jnp.ndarray] = None,  # [B, max_pages] (paged caches)
) -> Tuple[jnp.ndarray, Params]:
    P = stack_period(cfg)
    kinds, ffns = cfg.layer_kinds(), cfg.ffn_kinds()
    B = x.shape[0]
    pos_arr = _decode_positions(position, B)

    def group_fn(carry, inp):
        h = carry
        group_params, group_cache = inp
        new_cache: Params = {}
        for j in range(P):
            sp = group_params[f"sub_{j}"]
            cj = group_cache[f"sub_{j}"]
            kind, ffn = kinds[j], ffns[j]
            h, normed2, cj = _sublayer_mix(sp, cj, h, pos_arr, position,
                                           page_tables, cfg, kind, ffn, window)
            if ffn != "none":
                if ffn == "dense":
                    if cfg.serve_sparse:
                        y2 = sparse_ffn_decode(sp["ffn"], sp["ffn_pred"], normed2, cfg)
                    else:
                        y2, _ = ffn_forward(sp["ffn"], normed2, cfg)
                else:
                    y2, _ = moe_lib.moe_forward(sp["ffn"], normed2, cfg)
                h = h + y2
            new_cache[f"sub_{j}"] = cj
        return h, new_cache

    x, new_cache = jax.lax.scan(group_fn, x, (stack, cache))
    return x, new_cache


# -- host-driven layerwise decode (offload serving hook) ---------------------------

def unstack_groups(tree: Params, cfg: ModelConfig) -> List[Params]:
    """Split a stacked {sub_j: [G, ...]} pytree into G per-group pytrees.

    Done once per served batch by the offload path so the per-token layer loop
    indexes views instead of re-slicing the stacked arrays every step."""
    G = cfg.n_layers // stack_period(cfg)
    return [jax.tree_util.tree_map(lambda a: a[g], tree) for g in range(G)]


def stack_groups(groups: List[Params]) -> Params:
    """Inverse of unstack_groups (restack along the scan axis)."""
    return jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves), *groups)


def stack_decode_step_layerwise(
    param_groups: List[Params],
    x: jnp.ndarray,            # [B, 1, d]
    position: jnp.ndarray,     # scalar int32 (shared) or [B] per-slot positions
    cache_groups: List[Params],
    cfg: ModelConfig,
    window: int = 0,
    ffn_override=None,         # (dense_layer_idx, normed2 [B,1,d]) -> y [B,1,d]
    page_tables: Optional[jnp.ndarray] = None,  # [B, max_pages] (paged caches)
) -> Tuple[jnp.ndarray, List[Params]]:
    """Python-loop decode step over unstacked layer groups.

    Identical math to `stack_decode_step`, but the loop runs on host so a
    caller can intercept every dense-FFN sublayer via `ffn_override` — the
    offload serving path computes those from flash bundle payloads (predict ->
    batched engine step -> sparse FFN) instead of the resident weights.
    `dense_layer_idx` counts dense FFN sublayers in (group, sublayer) order —
    the same order `stack_forward(capture_activations=True)` stacks
    `ffn_pre_act`, so calibration traces and serving agree on layer ids.
    `page_tables` routes attention sublayers through a paged arena exactly as
    in `stack_decode_step` — the one page table serves every layer group.

    Each sublayer's mixer, residual and norm2 run as one compiled call
    (`_sublayer_mix_jit`), or, on a paged arena, as three: `_attn_pre_jit`,
    the paged-decode kernel (its own module, dispatched from here), and
    `_attn_post_jit`. They run under a `repro.obs` `attention` span, and the
    FFN under an `ffn` span.
    """
    P = stack_period(cfg)
    kinds, ffns = cfg.layer_kinds(), cfg.ffn_kinds()
    B = x.shape[0]
    pos_arr = _decode_positions(position, B)
    cur_pos = jnp.asarray(position).astype(jnp.int32)
    tr = get_tracer()
    h = x
    dense_idx = 0
    new_groups: List[Params] = []
    for group_params, group_cache in zip(param_groups, cache_groups):
        new_cache: Params = {}
        for j in range(P):
            sp = group_params[f"sub_{j}"]
            cj = group_cache[f"sub_{j}"]
            kind, ffn = kinds[j], ffns[j]
            with tr.span("attention"):
                if _is_paged(cj):
                    q, cj = _attn_pre_jit(sp, cj, h, pos_arr, position,
                                          page_tables, cfg=cfg,
                                          write=_paged_write(cj))
                    out = _paged_attn(q, cj, page_tables, cur_pos)
                    h, normed2 = _attn_post_jit(sp, h, out, cfg=cfg, ffn=ffn)
                else:
                    h, normed2, cj = _sublayer_mix_jit(
                        sp, cj, h, pos_arr, position, page_tables, cfg=cfg,
                        kind=kind, ffn=ffn, window=window)
            if ffn != "none":
                with tr.span("ffn"):
                    if ffn == "dense":
                        if ffn_override is not None:
                            y2 = ffn_override(dense_idx, normed2)
                        elif cfg.serve_sparse:
                            y2 = sparse_ffn_decode(sp["ffn"], sp["ffn_pred"],
                                                   normed2, cfg)
                        else:
                            y2, _ = ffn_forward(sp["ffn"], normed2, cfg)
                        dense_idx += 1
                    else:
                        y2, _ = moe_lib.moe_forward(sp["ffn"], normed2, cfg)
                    h = h + y2
            new_cache[f"sub_{j}"] = cj
        new_groups.append(new_cache)
    return h, new_groups
