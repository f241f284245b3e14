"""Pallas TPU kernels: sliding-window and PAGED GQA decode attention
(flash-decode style).

`swa_decode_kernel`: one new token attends to a ring-buffer KV cache of width
W under a sliding window — the long_500k dense decode path. Online-softmax
accumulation over KV blocks; grid (B, KV_heads, W/blk) with fp32 (m, l, acc)
scratch in VMEM. Slot validity is positional: slot j holds position pos[j]; it
participates iff pos[j] >= 0 and cur - window < pos[j] <= cur. `cur` arrives
via scalar prefetch.

`paged_decode_kernel`: the paged-KV variant (vLLM-style PagedAttention). K/V
live in a physical page arena [num_pages+1, KV, page_size, hd]; each batch
row's logical pages are resolved through a scalar-prefetched page table
[B, max_pages] whose entries drive the K/V BlockSpec index maps — the page
gather IS the block DMA, the same scalar-prefetch-indexed-BlockSpec pattern as
`sparse_ffn_segments_fused_kernel`'s segment gather. Logical slot p*page_size+o
holds position p*page_size+o; validity is causal (slot <= cur[b]), identical to
`attend_full_cache`'s masking, so unallocated logical pages may point at the
null page (arena row num_pages) and contribute exactly zero. Optional int8
support dequantises per-(page, offset, head) scales in-kernel, post-DMA.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(cur_ref, q_ref, k_ref, v_ref, pos_ref, o_ref,
            m_ref, l_ref, acc_ref, *, window: int, blocks: int, scale: float):
    blk = pl.program_id(2)

    @pl.when(blk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]                    # [G, hd]
    k = k_ref[0, 0]                    # [blk, hd]
    v = v_ref[0, 0]                    # [blk, hd]
    pos = pos_ref[0]                   # [blk] int32
    cur = cur_ref[0]

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale   # [G, blk]
    valid = (pos >= 0) & (pos > cur - window) & (pos <= cur)
    s = jnp.where(valid[None, :], s, NEG_INF)

    m_prev = m_ref[...]                # [G, 1]
    m_new = jnp.maximum(m_prev[:, 0], jnp.max(s, axis=-1))[:, None]
    alpha = jnp.exp(m_prev - m_new)
    # exp(NEG_INF - NEG_INF) would be 1 for fully-masked blocks: force 0.
    p = jnp.where(valid[None, :], jnp.exp(s - m_new), 0.0)            # [G, blk]
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(blk == blocks - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def swa_decode_kernel(
    q: jnp.ndarray,          # [B, KV, G, hd] — grouped query heads
    k: jnp.ndarray,          # [B, KV, W, hd] — ring buffer
    v: jnp.ndarray,          # [B, KV, W, hd]
    pos: jnp.ndarray,        # [B, W] int32 position per slot (-1 empty)
    cur_pos: jnp.ndarray,    # [1] int32 current position (scalar prefetch)
    *,
    window: int,
    block_w: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    B, KV, G, hd = q.shape
    W = k.shape[2]
    assert W % block_w == 0, "wrapper must pad ring to block multiple"
    blocks = W // block_w
    grid = (B, KV, blocks)
    scale = hd ** -0.5
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, h, w, cur: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_w, hd), lambda b, h, w, cur: (b, h, w, 0)),
            pl.BlockSpec((1, 1, block_w, hd), lambda b, h, w, cur: (b, h, w, 0)),
            pl.BlockSpec((1, block_w), lambda b, h, w, cur: (b, w)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, h, w, cur: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),      # running max
            pltpu.VMEM((G, 1), jnp.float32),      # running denom
            pltpu.VMEM((G, hd), jnp.float32),     # output accumulator
        ],
    )
    kern = functools.partial(_kernel, window=window, blocks=blocks, scale=scale)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
    )(cur_pos, q, k, v, pos)


# -- paged-attention decode ----------------------------------------------------

def _paged_core(q, k, v, scale_row, cur, page, page_size, pages, scale,
                m_ref, l_ref, acc_ref, o_ref):
    """One page's online-softmax step. q: [G, hd]; k/v: [page_size, hd];
    scale_row: [page_size, 1] dequant scales (None for float arenas)."""
    @pl.when(page == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if scale_row is not None:
        k = k.astype(jnp.float32) * scale_row[0]
        v = v.astype(jnp.float32) * scale_row[1]
    s = jnp.dot(q.astype(jnp.float32), k.astype(jnp.float32).T,
                preferred_element_type=jnp.float32) * scale      # [G, page_size]
    offs = jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
    slot = page * page_size + offs                               # [1, page_size]
    valid = slot <= cur                    # causal; trash past cur masks away
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]                    # [G, 1]
    m_new = jnp.maximum(m_prev[:, 0], jnp.max(s, axis=-1))[:, None]
    alpha = jnp.exp(m_prev - m_new)
    # exp(NEG_INF - NEG_INF) would be 1 for fully-masked pages: force 0.
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)                # [G, page_size]
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v.astype(jnp.float32), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(page == pages - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_decode_kernel(
    q: jnp.ndarray,          # [B, KV, G, hd] — grouped query heads, one token
    k: jnp.ndarray,          # [num_pages + 1, KV, page_size, hd] page arena
    v: jnp.ndarray,          # (row num_pages is the null page)
    page_tables: jnp.ndarray,  # [B, max_pages] int32 physical page per logical
    cur_pos: jnp.ndarray,    # [B] int32 current position (scalar prefetch)
    k_scale: jnp.ndarray = None,  # [num_pages + 1, KV, page_size] (int8 arena)
    v_scale: jnp.ndarray = None,
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """Paged-attention decode: grid (B, KV, max_pages); the page-table entry
    for (b, p) selects the K/V (and scale) blocks via the scalar-prefetch
    index map, so each grid step DMAs exactly one physical page."""
    B, KV, G, hd = q.shape
    page_size = k.shape[2]
    pages = page_tables.shape[1]
    grid = (B, KV, pages)
    scale = hd ** -0.5
    quant = k_scale is not None
    in_specs = [
        pl.BlockSpec((1, 1, G, hd), lambda b, h, p, pt, cur: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, page_size, hd),
                     lambda b, h, p, pt, cur: (pt[b, p], h, 0, 0)),
        pl.BlockSpec((1, 1, page_size, hd),
                     lambda b, h, p, pt, cur: (pt[b, p], h, 0, 0)),
    ]
    if quant:
        # A unit axis before page_size makes the scale block's last two dims
        # (1, page_size), the full array dims, as Mosaic requires; a
        # (1, 1, page_size) block of [pages+1, KV, page_size] is refused.
        k_scale = k_scale.reshape(k_scale.shape[:2] + (1, page_size))
        v_scale = v_scale.reshape(v_scale.shape[:2] + (1, page_size))
        sspec = pl.BlockSpec((None, None, 1, page_size),
                             lambda b, h, p, pt, cur: (pt[b, p], h, 0, 0))
        in_specs += [sspec, sspec]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, h, p, pt, cur: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),      # running max
            pltpu.VMEM((G, 1), jnp.float32),      # running denom
            pltpu.VMEM((G, hd), jnp.float32),     # output accumulator
        ],
    )
    if quant:
        def kern(pt_ref, cur_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
                 m_ref, l_ref, acc_ref):
            b, page = pl.program_id(0), pl.program_id(2)
            scales = (ks_ref[...].T, vs_ref[...].T)          # [page_size, 1]
            _paged_core(q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], scales,
                        cur_ref[b], page, page_size, pages, scale,
                        m_ref, l_ref, acc_ref, o_ref)
        args = (page_tables, cur_pos, q, k, v, k_scale, v_scale)
    else:
        def kern(pt_ref, cur_ref, q_ref, k_ref, v_ref, o_ref,
                 m_ref, l_ref, acc_ref):
            b, page = pl.program_id(0), pl.program_id(2)
            _paged_core(q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], None,
                        cur_ref[b], page, page_size, pages, scale,
                        m_ref, l_ref, acc_ref, o_ref)
        args = (page_tables, cur_pos, q, k, v)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), jnp.float32),
        interpret=interpret,
    )(*args)
