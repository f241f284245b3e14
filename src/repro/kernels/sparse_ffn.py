"""Pallas TPU kernels: segment-gather sparse FFN, unfused and fused variants.

The TPU-native expression of RIPPLE's contiguous neuron links: the activated
neuron set is delivered as *segment ids* (each segment = `seg` consecutive
neurons in the permuted physical layout). A scalar-prefetch id array drives the
BlockSpec index_map, so each grid step DMAs one contiguous [seg, d_model] tile
of each weight matrix HBM->VMEM and feeds 128-aligned tiles to the MXU:

    y = sum_s act(x @ W_up[seg_s]^T) [* (x @ W_gate[seg_s]^T)] @ W_down[seg_s]

Contiguity => one DMA descriptor per segment per matrix — the same IOPS
argument as the paper's flash reads, at the HBM->VMEM tier.

Two kernel variants:

  * `sparse_ffn_segments_kernel` — the original float-tile kernel. Padding
    convention: the wrapper (ops.py) appends one all-zero segment at block
    index N/seg; padded entries of `seg_ids` point there and contribute 0.
    Exact only when act(pre <= 0) == 0 (relu/relu2): covered-but-inactive
    neurons inside a segment are computed unmasked.

  * `sparse_ffn_segments_fused_kernel` — int8-dequant + neuron-mask + FFN in
    one pass. Weight tiles may be int8 (the NeuronPack storage dtype) or any
    float dtype; a second gathered input `scale_tiles` [S, seg] float32
    carries a per-neuron multiplier = dequant scale x activated-mask. Each
    grid step upcasts its raw [seg, D] tiles in VMEM and multiplies by the
    scale column BEFORE the MXU dots:

        W_eff[seg_s] = raw_tile.astype(f32) * scale_tiles[s][:, None]

    so (a) int8 packs never materialize float32 rows outside VMEM — per-
    neuron symmetric quantization (format.py) makes q * scale the exact
    `dequantize_int8` value, and (b) a zero multiplier exactly zeroes a
    neuron's contribution for EVERY activation (act(0) == 0 for relu, relu2,
    gelu and silu; gated models also zero the gate), which is what makes the
    segment path exact for non-ReLU models: covered-but-not-activated
    neurons are masked in-kernel. Padded `seg_ids` entries are clamped to
    block 0 with an all-zero scale row — no appended zero segment needed.

int8 tile convention: tiles are the raw [seg, d_model] slices of the pack's
physical-order payload; `scale_tiles[s, j]` is the symmetric per-neuron scale
of physical neuron `seg_ids[s] * seg + j` (1.0 for float payloads), times 0/1
activated-union membership.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _apply_act(pre, name: str):
    if name == "relu":
        return jnp.maximum(pre, 0.0)
    if name == "relu2":
        return jnp.square(jnp.maximum(pre, 0.0))
    if name == "gelu":
        return jax.nn.gelu(pre)
    if name == "silu":
        return jax.nn.silu(pre)
    raise ValueError(name)


def _kernel(ids_ref, x_ref, up_ref, down_ref, o_ref, *, activation: str):
    s = pl.program_id(0)

    @pl.when(s == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    pre = jnp.dot(x_ref[...], up_ref[...].T,
                  preferred_element_type=jnp.float32)          # [B, seg]
    act = _apply_act(pre, activation)
    o_ref[...] += jnp.dot(act.astype(down_ref.dtype), down_ref[...],
                          preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _kernel_gated(ids_ref, x_ref, up_ref, gate_ref, down_ref, o_ref, *, activation: str):
    s = pl.program_id(0)

    @pl.when(s == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    pre = jnp.dot(x_ref[...], up_ref[...].T, preferred_element_type=jnp.float32)
    gate = jnp.dot(x_ref[...], gate_ref[...].T, preferred_element_type=jnp.float32)
    act = _apply_act(pre, activation) * gate
    o_ref[...] += jnp.dot(act.astype(down_ref.dtype), down_ref[...],
                          preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _kernel_fused(ids_ref, x_ref, scale_ref, up_ref, down_ref, o_ref, *, activation: str):
    s = pl.program_id(0)

    @pl.when(s == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    sv = scale_ref[...].astype(jnp.float32).T                   # [seg, 1]
    up = up_ref[...].astype(jnp.float32) * sv                   # dequant+mask in VMEM
    pre = jnp.dot(x_ref[...], up.T, preferred_element_type=jnp.float32)
    act = _apply_act(pre, activation)
    down = down_ref[...].astype(jnp.float32) * sv
    o_ref[...] += jnp.dot(act, down,
                          preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _kernel_fused_gated(ids_ref, x_ref, scale_ref, up_ref, gate_ref, down_ref, o_ref,
                        *, activation: str):
    s = pl.program_id(0)

    @pl.when(s == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    sv = scale_ref[...].astype(jnp.float32).T
    up = up_ref[...].astype(jnp.float32) * sv
    gate_w = gate_ref[...].astype(jnp.float32) * sv
    pre = jnp.dot(x_ref[...], up.T, preferred_element_type=jnp.float32)
    gate = jnp.dot(x_ref[...], gate_w.T, preferred_element_type=jnp.float32)
    act = _apply_act(pre, activation) * gate
    down = down_ref[...].astype(jnp.float32) * sv
    o_ref[...] += jnp.dot(act, down,
                          preferred_element_type=jnp.float32).astype(o_ref.dtype)


def sparse_ffn_segments_fused_kernel(
    x: jnp.ndarray,            # [B, D] float
    w_up: jnp.ndarray,         # [N, D] raw storage dtype (int8 or float)
    w_down: jnp.ndarray,       # [N, D]
    seg_ids: jnp.ndarray,      # [S] int32 block indices, pads pre-clamped to 0
    scale_tiles: jnp.ndarray,  # [S, seg] f32 per-neuron dequant-scale x mask
    w_gate: jnp.ndarray | None = None,
    *,
    seg_size: int = 128,
    activation: str = "relu",
    interpret: bool = False,
) -> jnp.ndarray:
    B, D = x.shape
    S = seg_ids.shape[0]
    wspec = pl.BlockSpec((seg_size, D), lambda s, ids: (ids[s], 0))
    # A (1, seg) block of an [S, seg] array breaks Mosaic's rule that a
    # block's last two dims be (8k, 128k) or the full array dims; a unit
    # middle axis makes them (1, seg), the full dims, for any seg.
    scale_tiles = scale_tiles.reshape(S, 1, seg_size)
    sspec = pl.BlockSpec((None, 1, seg_size), lambda s, ids: (s, 0, 0))
    in_specs = [
        pl.BlockSpec((B, D), lambda s, ids: (0, 0)),   # x resident in VMEM
        sspec,                                         # per-neuron multiplier
        wspec,                                         # up
    ]
    if w_gate is not None:
        in_specs.append(wspec)                         # gate
    in_specs.append(wspec)                             # down
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(S,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((B, D), lambda s, ids: (0, 0)),
    )
    kern = (functools.partial(_kernel_fused_gated, activation=activation)
            if w_gate is not None
            else functools.partial(_kernel_fused, activation=activation))
    args = ((seg_ids, x, scale_tiles, w_up)
            + ((w_gate,) if w_gate is not None else ()) + (w_down,))
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, D), jnp.float32),
        interpret=interpret,
    )(*args)


def sparse_ffn_segments_kernel(
    x: jnp.ndarray,          # [B, D]
    w_up: jnp.ndarray,       # [N + seg, D]  (zero pad segment appended)
    w_down: jnp.ndarray,     # [N + seg, D]
    seg_ids: jnp.ndarray,    # [S] int32 block indices into the segment axis
    w_gate: jnp.ndarray | None = None,
    *,
    seg_size: int = 128,
    activation: str = "relu",
    interpret: bool = False,
) -> jnp.ndarray:
    B, D = x.shape
    S = seg_ids.shape[0]
    wspec = pl.BlockSpec((seg_size, D), lambda s, ids: (ids[s], 0))
    in_specs = [
        pl.BlockSpec((B, D), lambda s, ids: (0, 0)),   # x resident in VMEM
        wspec,                                         # up
    ]
    if w_gate is not None:
        in_specs.append(wspec)                         # gate
    in_specs.append(wspec)                             # down
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(S,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((B, D), lambda s, ids: (0, 0)),
    )
    kern = (functools.partial(_kernel_gated, activation=activation) if w_gate is not None
            else functools.partial(_kernel, activation=activation))
    args = (seg_ids, x, w_up) + ((w_gate,) if w_gate is not None else ()) + (w_down,)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, D), jnp.float32),
        interpret=interpret,
    )(*args)
