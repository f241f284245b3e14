"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Mosaic, the TPU kernel compiler, checks what interpret mode and the CPU XLA
twins cannot: block shapes against the (8, 128) tiling, VMEM use, the dtype
rules of in-kernel ops. Each case lowers a public wrapper with
`interpret=False` for one chip of a described (not attached) v5e and asserts
the kernel is in the compiled module. The topology is described inside a
fixture, never while a module is imported: only one process may load the
TPU library, and a worker that cannot skips these tests.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

SEG = 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep such compiles out of it."""
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [None if s is None else
            jax.ShapeDtypeStruct(s[0], s[1], sharding=one_chip)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("d,n,dtype,gated", [
    (2048, 8192, jnp.float32, False),     # OPT-1.3B FFN, float payload
    (2048, 8192, jnp.int8, False),        # same, int8 NeuronPack payload
    (4096, 11008, jnp.float32, True),     # 7B gated (silu) FFN
], ids=["f32-d2048", "int8-d2048", "gated-f32-d4096"])
def test_fused_segment_ffn_compiles(one_chip, no_compile_cache,
                                    d, n, dtype, gated):
    S, B = 64, 8

    def fn(x, w_up, w_down, seg_ids, tiles, w_gate):
        return ops.sparse_ffn_segments_fused(
            x, w_up, w_down, seg_ids, tiles, w_gate, seg_size=SEG,
            activation="silu" if gated else "relu", interpret=False)

    hlo = _compile(fn, one_chip, ((B, d), jnp.float32), ((n, d), dtype),
                   ((n, d), dtype), ((S,), jnp.int32),
                   ((S, SEG), jnp.float32),
                   ((n, d), dtype) if gated else None)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("quant", [False, True], ids=["f32", "int8"])
def test_paged_decode_compiles(one_chip, no_compile_cache, quant):
    B, H, KV, hd, page, pages, max_pages = 4, 32, 32, 64, 16, 64, 8
    kv_dtype = jnp.int8 if quant else jnp.float32
    arena = ((pages + 1, page, KV, hd), kv_dtype)
    scale = ((pages + 1, page, KV), jnp.float32) if quant else None

    def fn(q, k, v, pt, cur, ks, vs):
        return ops.paged_decode_attention(q, k, v, pt, cur, ks, vs,
                                          interpret=False)

    hlo = _compile(fn, one_chip, ((B, H, hd), jnp.float32), arena, arena,
                   ((B, max_pages), jnp.int32), ((B,), jnp.int32),
                   scale, scale)
    assert "tpu_custom_call" in hlo
