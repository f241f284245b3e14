"""Serving engine + offloaded FFN runtime."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.engine import EngineConfig
from repro.core.pipeline import IOScheduler
from repro.core.placement import identity_placement
from repro.core.sparse_ffn import FFNWeights, dense_ffn, make_bundles
from repro.models import build_model
from repro.serving.engine import (OffloadedFFNRuntime, Request, ServingEngine,
                                  build_offload_runtime, sample_token)


def test_greedy_serving_matches_manual_decode(rng):
    cfg = get_config("granite-3-2b", reduced=True, vocab_size=128)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    prompt = rng.integers(0, 128, 8).astype(np.int32)
    engine = ServingEngine(model, params, max_len=64)
    [res] = engine.serve([Request(uid=0, prompt=prompt, max_new_tokens=5)])
    # manual greedy decode
    cache = model.init_cache(1, 64)
    logits, cache = model.prefill(params, {"tokens": jnp.asarray(prompt[None])}, cache)
    toks = []
    cur = jnp.argmax(logits[:, -1], -1)
    for i in range(5):
        toks.append(int(cur[0]))
        logits, cache = model.decode_step(params, cur[:, None].astype(jnp.int32),
                                          jnp.int32(8 + i), cache)
        cur = jnp.argmax(logits[:, 0], -1)
    assert res.tokens == toks
    assert res.prefill_seconds > 0 and res.decode_seconds > 0


def test_batched_requests_grouped(rng):
    cfg = get_config("granite-3-2b", reduced=True, vocab_size=64)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(1))
    engine = ServingEngine(model, params, max_len=48)
    reqs = [Request(uid=i, prompt=rng.integers(0, 64, 8).astype(np.int32),
                    max_new_tokens=3) for i in range(4)]
    results = engine.serve(reqs)
    assert sorted(r.uid for r in results) == [0, 1, 2, 3]
    assert all(len(r.tokens) == 3 for r in results)


def test_sample_token_temperature_zero_is_argmax():
    logits = jnp.asarray([[0.1, 2.0, -1.0]])
    assert int(sample_token(logits, 0.0, jax.random.PRNGKey(0))[0]) == 1


def test_offloaded_ffn_matches_dense(rng):
    """The engine's sparse FFN from flash bundles == dense FFN under ReLU."""
    d, n, L = 32, 256, 2
    cfg = get_config("granite-3-2b", reduced=True, d_model=d, activation="relu")
    ws = []
    bundles = []
    for _ in range(L):
        w = FFNWeights(
            w_up=jnp.asarray(rng.standard_normal((n, d)) * 0.2, jnp.float32),
            w_down=jnp.asarray(rng.standard_normal((n, d)) * 0.2, jnp.float32))
        ws.append(w)
        bundles.append(np.asarray(make_bundles(w)))
    placements = [identity_placement(n) for _ in range(L)]
    runtime = OffloadedFFNRuntime(cfg, bundles, placements,
                                  engine_cfg=EngineConfig(cache_ratio=0.2))
    h = rng.standard_normal((3, d)).astype(np.float32)
    for layer in range(L):
        pre = h @ np.asarray(ws[layer].w_up).T
        mask = pre > 0
        y, stats = runtime.ffn_apply(layer, h, oracle_mask=mask)
        ref = np.asarray(dense_ffn(jnp.asarray(h), ws[layer], activation="relu"))
        np.testing.assert_allclose(y, ref, rtol=1e-4, atol=1e-4)
        assert stats.n_activated == int(np.any(mask, axis=0).sum())
    summ = runtime.io_summary()
    assert summ["io_seconds_per_token"] > 0
    assert summ["ops_per_token"] >= 2   # one read batch per layer minimum


def test_ffn_apply_batch_matches_dense_per_request(rng):
    """Batched apply: per-request masks, one merged read, still exact."""
    d, n = 32, 256
    cfg = get_config("granite-3-2b", reduced=True, d_model=d, activation="relu")
    w = FFNWeights(
        w_up=jnp.asarray(rng.standard_normal((n, d)) * 0.2, jnp.float32),
        w_down=jnp.asarray(rng.standard_normal((n, d)) * 0.2, jnp.float32))
    runtime = OffloadedFFNRuntime(cfg, [np.asarray(make_bundles(w))],
                                  [identity_placement(n)])
    h = rng.standard_normal((4, d)).astype(np.float32)
    masks = np.asarray(h @ np.asarray(w.w_up).T > 0)
    y, res = runtime.ffn_apply_batch(0, jnp.asarray(h), masks)
    ref = np.asarray(dense_ffn(jnp.asarray(h), w, activation="relu"))
    np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-4, atol=1e-4)
    assert len(res.per_request) == 4
    assert res.merged.n_activated == int(np.any(masks, axis=0).sum())
    assert sum(rs.n_misses for rs in res.per_request) >= res.merged.n_misses


def _tiny_offload_setup(seed=0, n_layers=2):
    cfg = get_config("opt-350m", reduced=True, d_model=64, d_ff=256,
                     n_layers=n_layers, vocab_size=128)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    reqs = [Request(uid=i, prompt=rng.integers(0, 128, 8).astype(np.int32),
                    max_new_tokens=4) for i in range(3)]
    return cfg, model, params, reqs


def test_offload_serve_token_identical_to_resident():
    """Acceptance: mode='offload' under the oracle mask returns the resident
    path's tokens exactly, with Result.io_seconds > 0."""
    cfg, model, params, reqs = _tiny_offload_setup()
    res_resident = ServingEngine(model, params, max_len=32).serve(reqs)
    runtime = build_offload_runtime(model, params,
                                    rng=np.random.default_rng(1))
    engine = ServingEngine(model, params, max_len=32, mode="offload",
                           offload=runtime, scheduler=IOScheduler(overlap=True))
    res_offload = engine.serve(reqs)
    for a, b in zip(res_resident, res_offload):
        assert a.uid == b.uid
        assert a.tokens == b.tokens
        assert b.io_seconds > 0
        assert b.overlapped_seconds > 0
    p = engine.scheduler.summary()
    # max_new=4 => 3 batched decode iterations: the first token of each
    # request comes from its prefill, and the server never runs the old
    # path's trailing decode step whose sample was discarded
    assert p["tokens"] == 3
    assert p["overlapped_seconds_per_token"] <= p["serial_seconds_per_token"]
    assert runtime.io_summary()["io_seconds_per_token"] > 0


def test_unstack_stack_groups_roundtrip():
    import jax.tree_util as jtu
    from repro.models import transformer
    cfg, model, params, _ = _tiny_offload_setup(seed=4)
    groups = transformer.unstack_groups(params["stack"], cfg)
    assert len(groups) == cfg.n_layers // transformer.stack_period(cfg)
    restacked = transformer.stack_groups(groups)
    for a, b in zip(jtu.tree_leaves(params["stack"]), jtu.tree_leaves(restacked)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_offload_serve_overlap_off_equals_serial():
    cfg, model, params, reqs = _tiny_offload_setup(seed=3)
    runtime = build_offload_runtime(model, params, use_placement=False,
                                    rng=np.random.default_rng(2))
    engine = ServingEngine(model, params, max_len=32, mode="offload",
                           offload=runtime,
                           scheduler=IOScheduler(overlap=False))
    engine.serve(reqs)
    p = engine.scheduler.summary()
    assert p["overlapped_seconds_per_token"] == p["serial_seconds_per_token"]
    assert p["overlap_efficiency"] == 0.0


def test_mixed_temperature_group_honors_each_request(rng):
    """Satellite fix: both serve paths used group[0].temperature for every
    request. Greedy rows must stay exact argmax even when other rows in the
    same group sample at high temperature."""
    cfg = get_config("granite-3-2b", reduced=True, vocab_size=64)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(7))
    prompts = [rng.integers(0, 64, 8).astype(np.int32) for _ in range(3)]
    greedy_only = ServingEngine(model, params, max_len=48).serve(
        [Request(uid=i, prompt=p, max_new_tokens=4)
         for i, p in enumerate(prompts)], seed=0)
    mixed = ServingEngine(model, params, max_len=48).serve(
        [Request(uid=0, prompt=prompts[0], max_new_tokens=4, temperature=5.0),
         Request(uid=1, prompt=prompts[1], max_new_tokens=4),   # greedy
         Request(uid=2, prompt=prompts[2], max_new_tokens=4, temperature=2.0)],
        seed=0)
    # the greedy request is unaffected by its neighbours' temperatures
    assert mixed[1].tokens == greedy_only[1].tokens
    # sampling at high temperature actually samples (not argmax) for at
    # least one of the hot rows on this seed
    assert (mixed[0].tokens != greedy_only[0].tokens
            or mixed[2].tokens != greedy_only[2].tokens)


def test_sample_tokens_vectorized_per_row():
    from repro.serving.engine import sample_tokens
    logits = jnp.asarray([[0.1, 2.0, -1.0], [5.0, 0.0, 0.0]])
    toks = sample_tokens(logits, np.array([0.0, 0.0]), jax.random.PRNGKey(0))
    assert toks.tolist() == [1, 0]
    # greedy rows stay argmax in a mixed batch
    mixed = sample_tokens(logits, np.array([3.0, 0.0]), jax.random.PRNGKey(0))
    assert int(mixed[1]) == 0


def test_segment_kernel_serving_path_matches_bundles_on_permuted_layout(rng):
    """Satellite: EngineConfig.ffn_kernel='segments' routes the serving FFN
    through the Pallas segment-gather kernel (interpret mode on CPU) over the
    PERMUTED physical layout; under the ReLU oracle it must match both the
    bundle-payload path and the dense reference."""
    import numpy as _np
    d, n = 128, 512
    cfg = get_config("granite-3-2b", reduced=True, d_model=d, activation="relu")
    w = FFNWeights(
        w_up=jnp.asarray(rng.standard_normal((n, d)) * 0.2, jnp.float32),
        w_down=jnp.asarray(rng.standard_normal((n, d)) * 0.2, jnp.float32))
    bundles = np.asarray(make_bundles(w))
    perm = _np.random.default_rng(5).permutation(n).astype(np.int64)
    inv = np.empty(n, dtype=np.int64)
    inv[perm] = np.arange(n)
    from repro.core.placement import PlacementResult
    pl = PlacementResult(placement=perm, inverse=inv, edges_used=0,
                         search_seconds=0.0, mode="test-perm")
    rt_seg = OffloadedFFNRuntime(
        cfg, [bundles], [pl],
        engine_cfg=EngineConfig(ffn_kernel="segments", kernel_seg_size=128))
    rt_ref = OffloadedFFNRuntime(cfg, [bundles], [pl],
                                 engine_cfg=EngineConfig(ffn_kernel="bundles"))
    # "auto" promotes segments on this permuted (non-identity) layout
    rt_auto = OffloadedFFNRuntime(cfg, [bundles], [pl])
    assert rt_auto.ffn_kernel == "segments"
    h = rng.standard_normal((3, d)).astype(np.float32)
    masks = np.asarray(h @ np.asarray(w.w_up).T > 0)
    y_seg, res_seg = rt_seg.ffn_apply_batch(0, jnp.asarray(h), masks)
    y_ref, res_ref = rt_ref.ffn_apply_batch(0, jnp.asarray(h), masks)
    dense = np.asarray(dense_ffn(jnp.asarray(h), w, activation="relu"))
    np.testing.assert_allclose(np.asarray(y_seg), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(y_seg), dense, rtol=1e-4, atol=1e-4)
    # the kernel choice must not change the I/O accounting
    assert res_seg.merged.io.seconds == res_ref.merged.io.seconds
    # and it also serves the prefetch pipeline (imperfect speculation)
    spec = masks.copy()
    spec[:, ::4] = False
    rt_seg.start_prefetch()
    try:
        rt_seg.begin_layer(0, spec)
        y_pipe, _, _ = rt_seg.complete_layer(0, jnp.asarray(h), masks)
    finally:
        rt_seg.stop_prefetch()
    np.testing.assert_allclose(np.asarray(y_pipe), dense, rtol=1e-4, atol=1e-4)


def test_segment_kernel_exact_for_gated_silu(rng):
    """The fused segment kernel masks covered-but-not-activated neurons
    in-kernel (per-neuron scale tiles), so the former relu/relu2-only guard
    is gone: a gated silu arch on the segments path must match the bundles
    path AND the dense reference over the same activated set."""
    d, n = 32, 256
    cfg = get_config("granite-3-2b", reduced=True, d_model=d, activation="silu")
    w = FFNWeights(
        w_up=jnp.asarray(rng.standard_normal((n, d)) * 0.2, jnp.float32),
        w_down=jnp.asarray(rng.standard_normal((n, d)) * 0.2, jnp.float32),
        w_gate=jnp.asarray(rng.standard_normal((n, d)) * 0.2, jnp.float32))
    bundles = np.asarray(make_bundles(w))
    rt_seg = OffloadedFFNRuntime(cfg, [bundles], [identity_placement(n)],
                                 engine_cfg=EngineConfig(ffn_kernel="segments"))
    rt_ref = OffloadedFFNRuntime(cfg, [bundles], [identity_placement(n)],
                                 engine_cfg=EngineConfig(ffn_kernel="bundles"))
    h = rng.standard_normal((3, d)).astype(np.float32)
    # silu has no exact sparse support; serve a sparse activated subset and
    # compare against the masked dense computation over exactly that subset
    masks = rng.random((3, n)) < 0.2
    y_seg, _ = rt_seg.ffn_apply_batch(0, jnp.asarray(h), masks)
    y_ref, _ = rt_ref.ffn_apply_batch(0, jnp.asarray(h), masks)
    np.testing.assert_allclose(np.asarray(y_seg), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)
    union = np.any(masks, axis=0)
    pre = h @ np.asarray(w.w_up).T
    act = pre / (1 + np.exp(-pre)) * (h @ np.asarray(w.w_gate).T)
    dense_sub = (act * union[None, :]) @ np.asarray(w.w_down)
    np.testing.assert_allclose(np.asarray(y_seg), dense_sub,
                               rtol=1e-4, atol=1e-4)


def test_io_summary_aggregates_from_sums(rng):
    """Satellite fix: effective_bandwidth / cache_hit_rate were means of
    per-layer ratios; they must be traffic-weighted (summed numerators over
    summed denominators)."""
    d, n = 16, 128
    cfg = get_config("granite-3-2b", reduced=True, d_model=d, activation="relu")
    w = FFNWeights(
        w_up=jnp.asarray(rng.standard_normal((n, d)) * 0.2, jnp.float32),
        w_down=jnp.asarray(rng.standard_normal((n, d)) * 0.2, jnp.float32))
    bundles = np.asarray(make_bundles(w))
    runtime = OffloadedFFNRuntime(cfg, [bundles, bundles],
                                  [identity_placement(n), identity_placement(n)])
    h = rng.standard_normal((2, d)).astype(np.float32)
    masks = np.asarray(h @ np.asarray(w.w_up).T > 0)
    # drive layer 0 with 5x the traffic of layer 1
    for _ in range(5):
        runtime.ffn_apply_batch(0, jnp.asarray(h), masks)
    runtime.ffn_apply_batch(1, jnp.asarray(h), masks)
    summ = runtime.io_summary()
    tokens = [t for e in runtime.engines for t in e.history]
    io_s = sum(t.io.seconds for t in tokens)
    useful = sum(t.io.bytes_useful for t in tokens)
    hits = sum(e.cache.stats.hits for e in runtime.engines)
    accesses = sum(e.cache.stats.hits + e.cache.stats.misses
                   for e in runtime.engines)
    assert summ["effective_bandwidth"] == (useful / io_s if io_s else 0.0)
    assert summ["cache_hit_rate"] == hits / accesses


# -- compiled layerwise mixer ------------------------------------------------------

def _layerwise_tokens(cfg, params, cache_groups, page_tables, steps=6):
    """Greedy decode through `stack_decode_step_layerwise` from per-slot
    positions [0, 3]: (tokens [steps, B], logits [steps, B, V])."""
    from repro.models import transformer
    from repro.models.layers import apply_norm, embed_tokens, unembed
    groups = transformer.unstack_groups(params["stack"], cfg)
    tok = jnp.asarray([5, 9], jnp.int32)
    pos = jnp.asarray([0, 3], jnp.int32)
    toks, rows = [], []
    for _ in range(steps):
        x = embed_tokens(params["embed"], tok[:, None], cfg)
        h, cache_groups = transformer.stack_decode_step_layerwise(
            groups, x, pos, cache_groups, cfg, page_tables=page_tables)
        logits = unembed(params["embed"],
                         apply_norm(params["final_norm"], h, cfg), cfg)[:, 0]
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        toks.append(np.asarray(tok))
        rows.append(np.asarray(logits, np.float32))
        pos = pos + 1
    return np.stack(toks), np.stack(rows)


@pytest.mark.parametrize("cache", ["paged", "paged_int8", "contiguous", "swa"])
def test_compiled_layerwise_step_matches_eager(cache):
    """The layerwise step's compiled mixer (one jitted call a sublayer, or
    pre -> paged kernel -> post on a paged arena) gives the eager step's
    tokens, and its logits to float32 tolerance, on every cache kind."""
    from repro.models import transformer
    cfg = get_config("opt-350m", reduced=True, d_model=64, d_ff=256,
                     n_layers=2, vocab_size=128, sliding_window=4,
                     kv_quant=cache == "paged_int8")
    params = build_model(cfg).init_params(jax.random.PRNGKey(11))
    B, page_size, max_pages = 2, 4, 4
    page_tables = None
    if cache.startswith("paged"):
        stacked = transformer.init_paged_stack_cache(cfg, B * max_pages,
                                                     page_size)
        page_tables = jnp.arange(B * max_pages, dtype=jnp.int32).reshape(
            B, max_pages)
    else:
        stacked = transformer.init_stack_cache(cfg, B, page_size * max_pages,
                                               swa=cache == "swa")
    groups = transformer.unstack_groups(stacked, cfg)
    toks, rows = _layerwise_tokens(cfg, params, groups, page_tables)
    with jax.disable_jit():
        ref_toks, ref_rows = _layerwise_tokens(cfg, params, groups,
                                               page_tables)
    np.testing.assert_array_equal(toks, ref_toks)
    np.testing.assert_allclose(rows, ref_rows, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "contiguous"])
def test_compiled_mixer_traces_once_and_kernel_runs_every_step(monkeypatch,
                                                                paged):
    """Through the offload server, `model.mixer_traces` grows only in the
    first decode step (no retrace from a changed shape or a Python scalar),
    and the paged-decode kernel is dispatched once per attention sublayer
    per step at run time, not only while a trace runs: it stays its own
    module, the one the kernel's roofline reads."""
    from repro.kernels import ops
    from repro.models import transformer
    from repro.obs import get_metrics
    from repro.serving.server import InferenceServer
    jax.clear_caches()                  # the first step traces afresh
    cfg = get_config("opt-350m", reduced=True, d_model=64, d_ff=256,
                     n_layers=2, vocab_size=128)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(3))
    rt = build_offload_runtime(model, params, rng=np.random.default_rng(3))
    traces = get_metrics().counter("model.mixer_traces")
    kernel_calls = []
    real_kernel = ops.paged_decode_attention

    def kernel(*a, **kw):
        kernel_calls.append(1)
        return real_kernel(*a, **kw)

    steps = []                          # (traces added, kernel calls)
    real_step = transformer.stack_decode_step_layerwise

    def step(*a, **kw):
        t0, k0 = traces.value, len(kernel_calls)
        out = real_step(*a, **kw)
        steps.append((traces.value - t0, len(kernel_calls) - k0))
        return out

    monkeypatch.setattr(ops, "paged_decode_attention", kernel)
    monkeypatch.setattr(transformer, "stack_decode_step_layerwise", step)
    kw = dict(page_size=4, num_pages=24) if paged else {}
    server = InferenceServer(model, params, max_slots=2, max_len=48,
                             mode="offload", offload=rt, **kw)
    rng = np.random.default_rng(5)
    try:
        for uid, n in enumerate((5, 9)):
            server.submit(Request(uid=uid, prompt=rng.integers(1, 127, n)
                                  .tolist(), max_new_tokens=6))
        results = server.drain()
    finally:
        server.close()
    assert len(results) == 2
    assert len(steps) >= 5
    # pre and post on a paged arena, one fused call otherwise; P = 1 here
    assert steps[0][0] == (2 if paged else 1)
    assert [t for t, _ in steps[1:]] == [0] * (len(steps) - 1)
    assert [k for _, k in steps] == [cfg.n_layers if paged else 0] * len(steps)
