"""Serving building blocks: requests/results, sampling streams, and the
flash-offloaded FFN runtime shared by both serving front-ends.

Front-ends (see `repro.serving.server` for the primary one):
  * `InferenceServer` (server.py) — slot-based continuous batching with an
    explicit request lifecycle, mid-flight admission, per-request retirement,
    and streaming. The serving runtime proper.
  * `ServingEngine` (here) — the historic one-shot `serve()` API, kept as a
    thin submit-all + drain wrapper over InferenceServer.

Two modes, both front-ends:
  * resident  — all weights in device memory; jit'd prefill/decode only.
  * offload   — the paper's §5 online stage, end-to-end: prefill runs dense
    (the paper offloads only the memory-dominant decode FFN), then every
    decode step drives, per dense-FFN layer and for the WHOLE decode batch,
        predict activated neurons (trained predictor or exact ReLU oracle)
        -> one batched engine step (merged cache probe + single collapsed
           extent read over the simulated UFS layout)
        -> sparse FFN computed from the bundle payloads actually read.

The offload mode EXECUTES the paper's I/O–compute overlap when built with
`prefetch=True`: a background I/O worker runs layer k+1's engine begin phase
(cache probe + collapsed read + staging gather into a double-buffered host
ring) while the device computes layer k's FFN, driven by a cross-layer
lookahead predictor (layer k's pre-FFN hidden -> layer k+1's mask). The
serving thread reconciles each prefetched layer against the true mask — any
mis-predicted neuron is served by a synchronous top-up read, so pipelined
decode is never less exact than serial. `IOScheduler` reports BOTH the
analytic double-buffered schedule (modeled UFS read times) and the MEASURED
overlap (worker busy time vs serving-thread wait time vs token wall clock);
in prefetch mode `Result.overlapped_seconds` carries the measured per-token
wall clock — what actually happened, not a model. Per-request I/O is
attributed by the engine and lands in `Result.io_seconds`.

The offload path intentionally runs layer-by-layer on host (it models a
phone-style single-device runtime); the distributed pjit path is the dense
one exercised by launch/dryrun.py.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Any, Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.engine import (BatchStepResult, EngineConfig, OffloadEngine,
                               PendingStep)
from repro.core.pipeline import IOScheduler, StageMeasurement
from repro.core.placement import PlacementResult
from repro.core.predictor import (PredictorParams, predict_mask,
                                  train_lookahead_predictors)
from repro.core.sparse_ffn import sparse_ffn_from_bundles
from repro.core.storage import NeuronStore, UFSDevice
from repro.models import transformer
from repro.models.model import Model
from repro.obs import get_metrics, get_tracer


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray          # [T] int32
    max_new_tokens: int = 16
    temperature: float = 0.0
    # generation stops the step any of these tokens is sampled (the stop token
    # IS included in the output); honored in resident and offload decode alike
    stop_tokens: tuple = ()
    # -- SLO surface (InferenceServer; ignored by the one-shot serve() path) --
    # admission priority class: higher admits first, and a full queue sheds
    # strictly-lower-priority queued work before rejecting a newcomer
    priority: int = 0
    # deadlines on the server's monotonic clock, None = server default/none:
    # TTFT (submit -> first token) and max inter-token gap; a blown deadline
    # retires the request with finish_reason="timeout", partial tokens kept
    ttft_slo_s: Optional[float] = None
    itl_slo_s: Optional[float] = None


@dataclasses.dataclass
class Result:
    uid: int
    tokens: List[int]
    prefill_seconds: float
    decode_seconds: float
    io_seconds: float = 0.0            # this request's attributed flash I/O
    # Pipelined decode latency summed over the decode iterations this request
    # was active in. In prefetch mode this is MEASURED: the per-token wall
    # clock of the real overlap pipeline (worker I/O running under device
    # compute) — scheduler.summary()'s measured_* keys carry the
    # reconciliation against the analytic model. In serial offload mode it is
    # the modeled double-buffered schedule (stage compute from the measured
    # token wall apportioned by FLOPs, stage io from the UFS model).
    overlapped_seconds: float = 0.0
    # "length"  — max_new_tokens generated (the normal completion)
    # "stop"    — a stop token was sampled (included in the output)
    # "error"   — an exception retired this request (per-request isolation)
    # "timeout" — an SLO deadline (TTFT or inter-token) expired; partial
    #             tokens are preserved (InferenceServer only)
    # "rejected"— backpressure: the admission queue was full at submit time,
    #             or this queued request was shed for a higher-priority
    #             arrival; no tokens were generated (InferenceServer only)
    finish_reason: str = "length"
    # set iff finish_reason == "error": the exception that retired this
    # request (per-request isolation — co-batched requests keep decoding)
    error: Optional[BaseException] = None


def request_key(base_key, uid: int):
    """Per-request sampling stream root: `fold_in(serve seed, uid)`.

    Token t of request `uid` is sampled from `fold_in(request_key(...), t)`,
    so a request's sampled tokens depend only on (seed, uid, t) and its own
    logits — NOT on which batch, group, or decode slot the request landed in
    (grouping-invariant sampling)."""
    return jax.random.fold_in(base_key, uid)


def sample_tokens(logits: jnp.ndarray, temperatures, key) -> jnp.ndarray:
    """Per-row sampling: row i is greedy if temperatures[i] <= 0, else
    categorical at its own temperature — one vectorized call for the whole
    decode batch, so mixed-temperature groups need no per-request loop."""
    hot = np.asarray(temperatures) > 0
    greedy_all = not bool(hot.any())
    greedy = jnp.argmax(logits, axis=-1)
    if greedy_all:                  # common all-greedy case: skip sampling
        return greedy
    temps = jnp.asarray(temperatures, dtype=logits.dtype)
    safe = jnp.where(jnp.asarray(hot), temps, jnp.ones_like(temps))
    sampled = jax.random.categorical(key, logits / safe[:, None], axis=-1)
    return jnp.where(jnp.asarray(hot), sampled, greedy)


def sample_token(logits: jnp.ndarray, temperature: float, key) -> jnp.ndarray:
    """Single shared temperature for every row (legacy helper)."""
    return sample_tokens(logits, np.full((logits.shape[0],), temperature,
                                         dtype=np.float32), key)


# ---------------------------------------------------------------------------
# Offloaded FFN runtime: per-layer engines + batched apply + prefetch pipeline
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PrefetchedLayer:
    """One layer's staged prefetch, produced by the I/O worker: the engine's
    pending split-phase step plus where its payload sits in the staging ring."""
    layer: int
    pending: PendingStep
    k_spec: int                  # staged rows [0, k_spec) = speculated union
    io_host_seconds: float = 0.0  # measured worker wall time for this layer


class PrefetchWorker:
    """Background I/O thread for layer-ahead prefetch.

    The serving thread submits (layer, speculated masks) jobs; the worker
    runs the engine's begin phase (cache probe + read planning + collapsed
    read accounting) and gathers the speculated union's payload into the
    runtime's double-buffered staging ring, then posts the result. Jobs and
    results ride bounded queues (depth 2 = one job in flight + one queued),
    so a stalled consumer can never accumulate unbounded staged state.
    `Exception`s are caught per job on the worker and re-raised on the
    serving thread at `wait()` — the worker survives a failed job, so one
    bad read never costs the pipeline its thread. Non-`Exception` errors
    (`FatalFault`, MemoryError-class havoc) kill the thread; the runtime's
    supervision in `complete_layer` detects the death, restarts the worker
    within its budget, and serves the affected layers through the
    synchronous fallback.
    """

    _SENTINEL = object()

    def __init__(self, runtime: "OffloadedFFNRuntime") -> None:
        self._runtime = runtime
        self._jobs: "queue.Queue" = queue.Queue(maxsize=2)
        self._results: "queue.Queue" = queue.Queue(maxsize=2)
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="ripple-prefetch")
        self._thread.start()

    def submit(self, layer: int, masks: np.ndarray) -> None:
        self._jobs.put((layer, masks))

    def wait(self, layer: int) -> PrefetchedLayer:
        """Block until `layer`'s prefetch lands; re-raises worker exceptions.
        Raises RuntimeError promptly (sub-100ms poll) if the worker thread
        died — the supervision hook in `complete_layer` turns that into a
        restart + synchronous fallback instead of a crashed batch."""
        while True:
            try:
                kind, lay, payload = self._results.get(timeout=0.1)
                break
            except queue.Empty:
                if not self._thread.is_alive():
                    raise RuntimeError("prefetch worker died unexpectedly")
        if kind == "exc":
            raise payload
        if lay != layer:
            raise RuntimeError(f"prefetch out of order: wanted layer {layer}, "
                               f"got {lay}")
        return payload

    def _loop(self) -> None:
        while True:
            job = self._jobs.get()
            if job is self._SENTINEL:
                return
            layer, masks = job
            try:
                # span lands on the worker's own thread track, so the exported
                # trace shows layer k+1's read overlapping layer k's compute
                with get_tracer().span("prefetch", layer=layer) as sp:
                    t0 = time.perf_counter()
                    staged = self._runtime._stage_layer(layer, masks)
                    staged.io_host_seconds = time.perf_counter() - t0
                    sp.set(n_staged=staged.k_spec)
                self._results.put(("ok", layer, staged))
            except Exception as e:  # noqa: BLE001 — re-raised at wait();
                # BaseException (FatalFault & co.) deliberately falls
                # through and kills the thread: that is the worker-death
                # path supervision exists for.
                self._results.put(("exc", layer, e))

    def shutdown(self) -> None:
        # A dead worker may leave the bounded job queue full; put with a
        # short timeout and re-check aliveness so shutdown never deadlocks
        # behind a queue nobody is draining. While waiting for the join,
        # keep draining stale results: a worker whose staged results were
        # abandoned (supervision fallback) may be blocked on the bounded
        # result queue and needs a consumer to reach the sentinel.
        deadline = time.monotonic() + 30.0
        sent = False
        while self._thread.is_alive() and time.monotonic() < deadline:
            if not sent:
                try:
                    self._jobs.put_nowait(self._SENTINEL)
                    sent = True
                except queue.Full:
                    pass
            try:
                self._results.get_nowait()
            except queue.Empty:
                pass
            self._thread.join(timeout=0.05)

    @property
    def alive(self) -> bool:
        return self._thread.is_alive()


def _resolve_ffn_kernel(requested: str, placements: List[PlacementResult],
                        bundle_width: int, expected_width: int) -> tuple:
    """Resolve EngineConfig.ffn_kernel to a concrete path + human reason.

    "auto" promotes the fused segment kernel exactly when the layout can
    profit from it: every layer's placement is physical-placement-ordered
    (mode != "identity" — an identity layout carries no co-activation links,
    so segment blocks would cover mostly-inactive neurons) AND the stored
    bundle width maps onto [n_mats * d_model] weight rows (accounting-only
    stores with synthetic widths cannot be reshaped into FFN matrices).
    The segment path is exact for all supported activations: covered-but-
    not-activated neurons are masked in-kernel by the fused scale tiles.
    """
    if requested == "bundles":
        return "bundles", "explicitly requested"
    if requested == "segments":
        if bundle_width != expected_width:
            raise ValueError(
                f"ffn_kernel='segments' needs bundle_width == n_mats*d_model "
                f"({expected_width}), store has {bundle_width}")
        return "segments", "explicitly requested"
    if requested != "auto":
        raise ValueError(f"unknown ffn_kernel {requested!r}")
    if bundle_width != expected_width:
        return "bundles", (f"bundle_width {bundle_width} != n_mats*d_model "
                           f"{expected_width}: payload is not segment-mappable")
    modes = sorted({p.mode for p in placements})
    if not modes or "identity" in modes:
        return "bundles", ("identity layout: physical order carries no "
                           "co-activation links to exploit")
    return "segments", (f"physical-placement-ordered layout "
                        f"(modes: {', '.join(modes)})")


class OffloadedFFNRuntime:
    """Per-layer RIPPLE offload state: engines, predictors, placements,
    lookahead predictors, and the prefetch staging ring."""

    def __init__(
        self,
        cfg: ModelConfig,
        bundles_per_layer: Optional[List[np.ndarray]] = None,  # [L][n, width]
        placements: Optional[List[PlacementResult]] = None,
        predictors: Optional[List[PredictorParams]] = None,
        device: Optional[UFSDevice] = None,
        engine_cfg: Optional[EngineConfig] = None,
        lookahead: Optional[List[PredictorParams]] = None,
        lookahead_threshold: float = 0.35,
        bundle_bytes: Optional[int] = None,
        *,
        stores: Optional[List[NeuronStore]] = None,
        max_worker_restarts: int = 2,
    ) -> None:
        """Either raw `bundles_per_layer` + `placements` (in-memory stores are
        built per layer) or prebuilt `stores` — e.g. `FileNeuronStore`s over a
        NeuronPack, the `from_pack` path."""
        self.cfg = cfg
        self.engine_cfg = engine_cfg or EngineConfig()
        if stores is not None:
            if bundles_per_layer is not None or placements is not None:
                raise ValueError("pass either prebuilt `stores` or raw "
                                 "bundles_per_layer/placements, not both")
            self.engines = [OffloadEngine.from_store(s, config=engine_cfg)
                            for s in stores]
        else:
            if bundles_per_layer is None or placements is None:
                raise ValueError("OffloadedFFNRuntime needs bundles_per_layer"
                                 " + placements, or `stores`")
            self.engines = [
                OffloadEngine(b, placement=pl, device=device,
                              config=engine_cfg, bundle_bytes=bundle_bytes)
                for b, pl in zip(bundles_per_layer, placements)
            ]
        self.predictors = predictors
        # cross-layer lookahead: lookahead[k] predicts layer k+1's mask from
        # layer k's pre-FFN hidden state (the prefetch pipeline's driver)
        self.lookahead = lookahead
        self.lookahead_threshold = lookahead_threshold
        self.n_mats = 3 if cfg.activation == "silu" else 2
        self.ffn_kernel, self.ffn_kernel_reason = _resolve_ffn_kernel(
            self.engine_cfg.ffn_kernel,
            [e.placement for e in self.engines],
            self.engines[0].store.bundle_width if self.engines else 0,
            self.n_mats * cfg.d_model)
        # staging ring: 2 pad-bucketed host buffers per (width, dtype), the
        # worker filling one slot while the serving thread consumes the other
        self._staging: Dict[tuple, np.ndarray] = {}
        self._worker: Optional[PrefetchWorker] = None
        self._segment_weights: Dict[int, tuple] = {}
        self._lookahead_np: Optional[List[tuple]] = None
        self.topup_total = 0       # neurons served by synchronous top-up reads
        # prefetch supervision: on worker death, restart up to
        # `max_worker_restarts` times per prefetch session, then disable the
        # worker and serve every remaining layer through the synchronous
        # fallback. `worker_restarts`/`degraded_steps` are the reporting
        # counters (io_summary); `_inflight` tracks which layers have a
        # submitted-but-not-completed prefetch so completion knows whether a
        # staged result exists to wait for.
        self.max_worker_restarts = max_worker_restarts
        self.worker_restarts = 0
        self.degraded_steps = 0
        self._worker_disabled = False
        self._restarts_used = 0
        self._inflight: set = set()
        # per-window readings of the served set against the true one
        # (`offload.true_union_neurons`, counted by the layer engines): the
        # neurons the segment kernel serves and the segment rows it reads
        reg = get_metrics()
        self._served_neurons = reg.counter("offload.served_neurons")
        self._segment_rows = reg.counter("offload.segment_rows")

    @classmethod
    def from_pack(
        cls,
        cfg: ModelConfig,
        pack,                               # path | NeuronPack
        device: Optional[UFSDevice] = None,
        engine_cfg: Optional[EngineConfig] = None,
        predictors: Optional[List[PredictorParams]] = None,
        lookahead: Optional[List[PredictorParams]] = None,
        lookahead_threshold: float = 0.35,
        verify_checksums: bool = False,
        retry=None,
        fault_plans=None,
        max_worker_restarts: int = 2,
    ) -> "OffloadedFFNRuntime":
        """Serve straight from an on-disk NeuronPack artifact: one
        `FileNeuronStore` per layer, placements read from the pack, every
        collapsed extent a REAL positional file read. Raises ValueError when
        the pack's geometry does not match the model config (layer count,
        neuron count, bundle width).

        `verify_checksums=True` has every store check each extent read
        against the pack's per-bundle CRC32 table (v2 packs only; detected
        corruption triggers a re-read). `retry` overrides the stores'
        transient-failure `RetryPolicy`; `fault_plans` (one
        `repro.store.faults.FaultPlan` per layer, None entries allowed)
        arms deterministic fault injection below the retry layer — the
        chaos-test hook."""
        from repro.store.file_store import FileNeuronStore
        from repro.store.format import NeuronPack

        pack = NeuronPack.open(pack)
        validate_pack_for_model(pack, cfg)
        ecfg = engine_cfg or EngineConfig()
        if fault_plans is not None and len(fault_plans) != pack.n_layers:
            raise ValueError(f"fault_plans covers {len(fault_plans)} layers, "
                             f"pack has {pack.n_layers}")
        stores = [FileNeuronStore(
                      pack, l, device=device,
                      reads_per_bundle=ecfg.reads_per_bundle,
                      retry=retry, verify_checksums=verify_checksums,
                      fault_plan=fault_plans[l] if fault_plans else None)
                  for l in range(pack.n_layers)]
        return cls(cfg, stores=stores, predictors=predictors,
                   engine_cfg=engine_cfg, lookahead=lookahead,
                   lookahead_threshold=lookahead_threshold,
                   max_worker_restarts=max_worker_restarts)

    # -- single merged activated set (legacy accounting interface) ----------
    def ffn_apply(self, layer: int, h: np.ndarray, oracle_mask: Optional[np.ndarray] = None):
        """h: [B, d]. Returns (y [B, d], TokenStats).

        Activated set = predictor(h) if trained, else oracle mask (exact ReLU
        support, what the paper's predictor approximates with ~high recall).
        The payload is gathered into the same reused pad-bucketed staging
        buffer as the batched path — no fresh concatenation allocs.
        """
        if oracle_mask is None:
            assert self.predictors is not None, "need predictor or oracle mask"
            oracle_mask = np.asarray(predict_mask(self.predictors[layer], jnp.asarray(h)))
        ids = np.nonzero(np.any(np.atleast_2d(oracle_mask), axis=0))[0]
        _, stats = self.engines[layer].step(ids, fetch_payload=False)
        y = self._ffn_compute(layer, jnp.asarray(h), ids)
        return np.asarray(y), stats

    # -- whole decode batch, per-request attribution -------------------------
    def ffn_apply_batch(
        self,
        layer: int,
        h: jnp.ndarray,                            # [B, d]
        masks: Optional[np.ndarray] = None,        # [B, n_neurons] bool
    ) -> tuple[jnp.ndarray, BatchStepResult]:
        """One batched engine step for all B requests' activated sets.

        Returns (y [B, d], BatchStepResult). The FFN is computed once over
        the union payload — rows not activated for a request contribute 0
        under ReLU, and over-coverage from sharing neurons across requests is
        exact for the same reason. The engine consumes the mask matrix
        directly (`step_masks`) and the union payload is gathered into a
        reused pad-bucketed staging buffer: one buffer fill + one
        host-to-device transfer per layer, no per-request id lists and no
        fresh concatenation allocs in the decode inner loop.
        """
        if masks is None:
            assert self.predictors is not None, "need predictors or oracle masks"
            masks = np.asarray(predict_mask(self.predictors[layer], h))
        masks = np.atleast_2d(np.asarray(masks))
        res = self.engines[layer].step_masks(masks, fetch_payload=False)
        y = self._ffn_compute(layer, h, res.ids)
        return y, res

    # -- asynchronous layer-ahead prefetch -----------------------------------
    def start_prefetch(self) -> None:
        """Spin up a fresh I/O worker (one per served group: a clean worker
        means no stale staged state can leak across serve calls). A no-op
        while the worker is supervision-disabled (restart budget exhausted
        mid-run): the serving loop re-checks `prefetch_active` every step
        and must NOT be allowed to reset the budget until the run ends
        (`stop_prefetch` re-arms it)."""
        if self._worker_disabled:
            return
        if self._worker is not None:
            self.stop_prefetch()
        self._inflight.clear()
        self._worker = PrefetchWorker(self)

    def stop_prefetch(self) -> None:
        """Shut the worker down and re-arm supervision for the next run."""
        if self._worker is not None:
            self._worker.shutdown()
            self._worker = None
        self._inflight.clear()
        self._worker_disabled = False
        self._restarts_used = 0

    @property
    def prefetch_active(self) -> bool:
        return self._worker is not None and self._worker.alive

    def begin_layer(self, layer: int, masks: np.ndarray) -> None:
        """Submit a (possibly speculative) prefetch for `layer` to the
        worker. Degrades instead of crashing: with no live worker (never
        started, died and found dead here, or supervision-disabled) the
        submission is skipped and `complete_layer` serves the layer through
        the synchronous fallback."""
        if self._worker is not None and not self._worker.alive:
            self._handle_worker_death(
                RuntimeError("prefetch worker found dead at submit"))
        if self._worker is None:
            return
        self._worker.submit(layer, masks)
        self._inflight.add(layer)

    def predict_lookahead(self, layer: int, h_np: np.ndarray) -> np.ndarray:
        """Speculative mask for `layer + 1` from layer `layer`'s pre-FFN
        hidden state, evaluated in pure numpy on cached host-side predictor
        params — no jax dispatch competing with the decode computation."""
        from repro.core.predictor import as_numpy_params, predict_mask_np
        if self._lookahead_np is None:
            self._lookahead_np = [as_numpy_params(p) for p in self.lookahead]
        return predict_mask_np(self._lookahead_np[layer], h_np,
                               threshold=self.lookahead_threshold)

    def _stage_layer(self, layer: int, masks: np.ndarray) -> PrefetchedLayer:
        """Worker-side: engine begin phase + staging gather into ring slot
        `layer % 2` (consecutive layers alternate slots, so the serving
        thread's buffer is never the one the worker is filling)."""
        eng = self.engines[layer]
        pending = eng.begin_step_masks(masks, fetch_payload=False)
        k = int(pending.union.size)
        if self.ffn_kernel != "segments":
            store = eng.store
            padded = -(-max(k, 1) // self.PAD_BUCKET) * self.PAD_BUCKET
            # dtype-faithful staging: the ring slot is allocated at the RAW
            # stored dtype, so int8 pack rows stay int8 from pread to device
            # transfer; the companion scale slot rides along and the dequant
            # happens on-device inside sparse_ffn_from_bundles.
            buf = self._ring_slot(store.bundle_width, store.stored_dtype,
                                  padded, layer % 2)
            store.fetch_into(pending.union, buf)
            buf[k:padded] = 0
            if store.quantized:
                sbuf = self._scale_slot(padded, layer % 2)
                store.fetch_scales_into(pending.union, sbuf)
                sbuf[k:padded] = 0
        return PrefetchedLayer(layer=layer, pending=pending, k_spec=k)

    def _handle_worker_death(self, exc: BaseException) -> None:
        """Supervision: the worker thread died (non-Exception fault, OOM,
        ...). All in-flight prefetches are lost with the thread's queues;
        restart within the per-run budget, else disable the worker for the
        rest of the run (every remaining layer serves synchronously)."""
        from repro.utils import logger
        old, self._worker = self._worker, None
        self._inflight.clear()
        if old is not None:
            old.shutdown()
        if self._restarts_used < self.max_worker_restarts:
            self._restarts_used += 1
            self.worker_restarts += 1
            logger.warning(
                "prefetch worker died (%s); restarting (%d/%d)",
                exc, self._restarts_used, self.max_worker_restarts)
            self._worker = PrefetchWorker(self)
        else:
            self._worker_disabled = True
            logger.warning(
                "prefetch worker died (%s); restart budget (%d) exhausted — "
                "decode continues on the synchronous fallback path",
                exc, self.max_worker_restarts)

    def _complete_degraded(
        self, layer: int, h: jnp.ndarray, true_masks: np.ndarray,
    ) -> tuple[jnp.ndarray, BatchStepResult, StageMeasurement]:
        """Synchronous fallback for a layer whose prefetch was lost (worker
        death or per-job failure): one full engine step against the TRUE
        masks plus FFN from a dedicated staging slot — the double-buffered
        ring slots may still hold a live prefetch for a neighbouring layer,
        which must not be clobbered. Output is exact: the payload comes
        from the same store reads the serial path would issue."""
        t0 = time.perf_counter()
        get_tracer().instant("degraded_layer", layer=layer)
        masks = np.atleast_2d(np.asarray(true_masks))
        res = self.engines[layer].step_masks(masks, fetch_payload=False)
        y = self._ffn_compute(layer, h, res.ids, staging_slot="degraded")
        res.merged.io.degraded_steps += 1
        self.degraded_steps += 1
        meas = StageMeasurement(topup_seconds=time.perf_counter() - t0)
        return y, res, meas

    def complete_layer(
        self, layer: int, h: jnp.ndarray, true_masks: np.ndarray,
    ) -> tuple[jnp.ndarray, BatchStepResult, StageMeasurement]:
        """Serving-thread side: wait for `layer`'s prefetch, reconcile against
        the true masks (synchronous top-up read for lookahead misses — the
        mis-predicted payload is fetched and merged before compute, never
        skipped), and evaluate the FFN from the staged ring buffer.

        Fault-tolerant: a layer with no staged prefetch (worker dead /
        disabled / submission skipped), a per-job worker exception, or a
        worker death while waiting all land in `_complete_degraded` — the
        step is served synchronously and decode continues, token-identical
        whenever the underlying payload reads stay correct.
        """
        if self._worker is None or layer not in self._inflight:
            return self._complete_degraded(layer, h, true_masks)
        t0 = time.perf_counter()
        try:
            with get_tracer().span("prefetch_wait", layer=layer):
                pf = self._worker.wait(layer)
        except Exception as e:
            from repro.utils import logger
            self._inflight.discard(layer)
            if self._worker is not None and not self._worker.alive:
                self._handle_worker_death(e)
            else:
                # per-job failure: the worker survived, only this layer's
                # staged read is lost; later in-flight layers stay valid.
                logger.warning("prefetch for layer %d failed (%s); serving "
                               "synchronously", layer, e)
            return self._complete_degraded(layer, h, true_masks)
        self._inflight.discard(layer)
        blocked = time.perf_counter() - t0
        eng = self.engines[layer]
        t1 = time.perf_counter()
        res = eng.complete_step(pf.pending, true_masks)
        extra = res.topup_ids
        self.topup_total += int(extra.size)
        k_total = pf.k_spec + int(extra.size)
        if self.ffn_kernel == "segments":
            served = (pf.pending.union if extra.size == 0
                      else np.concatenate([pf.pending.union, extra]))
            topup = time.perf_counter() - t1
            y = self._ffn_segments(layer, h, served)
        else:
            store = eng.store
            padded = -(-max(k_total, 1) // self.PAD_BUCKET) * self.PAD_BUCKET
            buf = self._ring_slot(store.bundle_width, store.stored_dtype,
                                  padded, layer % 2, preserve_rows=pf.k_spec)
            if extra.size:   # stage the topped-up payload after the prefetch
                store.fetch_into(extra, buf[pf.k_spec:])
            buf[k_total:padded] = 0
            scales = None
            if store.quantized:
                sbuf = self._scale_slot(padded, layer % 2,
                                        preserve_rows=pf.k_spec)
                if extra.size:
                    store.fetch_scales_into(extra, sbuf[pf.k_spec:])
                sbuf[k_total:padded] = 0
                scales = jnp.asarray(sbuf[:padded])
            topup = time.perf_counter() - t1
            valid = jnp.arange(padded) < k_total
            y = sparse_ffn_from_bundles(
                h, jnp.asarray(buf[:padded]), self.cfg.d_model, self.n_mats,
                activation=self.cfg.activation, valid_mask=valid,
                scales=scales)
        meas = StageMeasurement(io_host_seconds=pf.io_host_seconds,
                                blocked_seconds=blocked, topup_seconds=topup)
        return y, res, meas

    # activated-set sizes vary every (step, layer); without bucketing each
    # fresh size triggers a new XLA compilation of the sparse-FFN matmuls.
    PAD_BUCKET = 128

    def _ring_slot(self, width: int, dtype, padded: int, slot: int,
                   preserve_rows: int = 0) -> np.ndarray:
        """One slot of the double-buffered staging ring (pad-bucketed host
        buffers, grown geometrically, shared by all layers of equal bundle
        width). `preserve_rows` keeps already-staged leading rows across a
        growth reallocation (the top-up append path)."""
        key = (width, dtype, slot)
        buf = self._staging.get(key)
        if buf is None or buf.shape[0] < padded:
            size = max(padded, 2 * buf.shape[0] if buf is not None else padded)
            new = np.zeros((size, width), dtype=dtype)
            if buf is not None and preserve_rows:
                new[:preserve_rows] = buf[:preserve_rows]
            buf = new
            self._staging[key] = buf
        return buf

    def _scale_slot(self, padded: int, slot: int,
                    preserve_rows: int = 0) -> np.ndarray:
        """Companion ring slot for per-neuron dequant scales (f32 [k]) —
        staged alongside each quantized payload slot so scales ride the same
        double-buffering discipline as the bundles they describe."""
        key = ("scales", slot)
        buf = self._staging.get(key)
        if buf is None or buf.shape[0] < padded:
            size = max(padded, 2 * buf.shape[0] if buf is not None else padded)
            new = np.zeros((size,), dtype=np.float32)
            if buf is not None and preserve_rows:
                new[:preserve_rows] = buf[:preserve_rows]
            buf = new
            self._staging[key] = buf
        return buf

    def _staging_buffer(self, width: int, dtype, padded: int) -> np.ndarray:
        """Serial-path staging buffer = slot 0 of the ring."""
        return self._ring_slot(width, dtype, padded, 0)

    def _ffn_compute(self, layer: int, h: jnp.ndarray, ids: np.ndarray,
                     staging_slot=0) -> jnp.ndarray:
        """Dispatch the resolved FFN path for an activated-union id list.
        `staging_slot` picks the host staging buffer: the degraded fallback
        uses its own slot so it can never clobber a ring slot holding a
        live neighbouring-layer prefetch."""
        if self.ffn_kernel == "segments":
            return self._ffn_segments(layer, h, ids)
        return self._ffn_from_ids(layer, h, ids, staging_slot)

    def _ffn_from_ids(self, layer: int, h: jnp.ndarray,
                      ids: np.ndarray, staging_slot=0) -> jnp.ndarray:
        store = self.engines[layer].store
        k = int(ids.size)
        padded = -(-max(k, 1) // self.PAD_BUCKET) * self.PAD_BUCKET
        buf = self._ring_slot(store.bundle_width,
                              store.stored_dtype, padded, staging_slot)
        store.fetch_into(ids, buf)
        buf[k:padded] = 0
        scales = None
        if store.quantized:
            sbuf = self._scale_slot(padded, staging_slot)
            store.fetch_scales_into(ids, sbuf)
            sbuf[k:padded] = 0
            scales = jnp.asarray(sbuf[:padded])
        valid = jnp.arange(padded) < k
        return sparse_ffn_from_bundles(
            h, jnp.asarray(buf[:padded]), self.cfg.d_model, self.n_mats,
            activation=self.cfg.activation, valid_mask=valid, scales=scales)

    # -- fused segment-gather kernel path (EngineConfig.ffn_kernel) ----------
    def _segment_weight_mats(self, layer: int) -> tuple:
        """Physical-layout weight matrices for the fused segment kernel,
        cached per layer: the store's RAW flash payload (int8 stays int8 —
        dequant happens in-kernel) reshaped into [N, d] up/down(/gate)
        matrices in placement order, zero-padded to a segment multiple, plus
        the host-side per-neuron base multipliers (dequant scales, or 1.0 for
        float payloads) in physical order."""
        cached = self._segment_weights.get(layer)
        if cached is not None:
            return cached
        store = self.engines[layer].store
        seg = self.engine_cfg.kernel_seg_size
        d = self.cfg.d_model
        parts = np.asarray(store.physical_payload(dequantize=False)).reshape(
            store.n_neurons, self.n_mats, d)
        pad = (-store.n_neurons) % seg
        if pad:
            parts = np.concatenate(
                [parts, np.zeros((pad,) + parts.shape[1:], parts.dtype)])
        base = np.ones(store.n_neurons + pad, dtype=np.float32)
        scales = store.physical_scales()
        if scales is not None:
            base[:store.n_neurons] = scales
        if self.n_mats == 3:     # bundle layout [gate | up | down]
            mats = (jnp.asarray(parts[:, 1]), jnp.asarray(parts[:, 2]),
                    jnp.asarray(parts[:, 0]), base)
        else:                    # [up | down]
            mats = (jnp.asarray(parts[:, 0]), jnp.asarray(parts[:, 1]),
                    None, base)
        self._segment_weights[layer] = mats
        return mats

    SEG_ID_BUCKET = 8

    def _ffn_segments(self, layer: int, h: jnp.ndarray,
                      ids: np.ndarray) -> jnp.ndarray:
        """FFN via the fused segment-gather kernel: the activated union maps
        to seg_size-aligned blocks of the PHYSICAL (placement-permuted)
        layout — contiguous links become few segments, the kernel's DMA
        argument. Exact for every supported activation: each segment carries
        a per-neuron multiplier tile (dequant scale x membership in the
        served union) applied to the weight rows in-kernel, so covered-but-
        not-activated neurons contribute exactly zero and int8 payloads are
        dequantized in VMEM, never on the host. Consumes two reused host
        buffers (segment ids + scale tiles) via jnp.asarray — no fresh
        concatenate/pad in the decode loop."""
        from repro.kernels import ops
        return ops.sparse_ffn_segments_fused(
            h, *self.segment_kernel_inputs(layer, ids),
            seg_size=self.engine_cfg.kernel_seg_size,
            activation=self.cfg.activation)

    def segment_kernel_inputs(self, layer: int, ids: np.ndarray) -> tuple:
        """`(w_up, w_down, seg_ids, scale_tiles, w_gate)`: the weight and
        segment arguments `ops.sparse_ffn_segments_fused` takes to serve the
        activated neuron `ids` (logical) at dense `layer`."""
        eng = self.engines[layer]
        seg = self.engine_cfg.kernel_seg_size
        w_up, w_down, w_gate, base = self._segment_weight_mats(layer)
        phys = eng.placement.physical_of(np.asarray(ids, dtype=np.int64))
        seg_of = phys // seg
        seg_u = np.unique(seg_of)
        S = int(seg_u.size)
        self._served_neurons.inc(int(phys.size))
        self._segment_rows.inc(S * seg)
        padded = -(-max(S, 1) // self.SEG_ID_BUCKET) * self.SEG_ID_BUCKET
        id_buf = self._seg_ids_buf(padded)
        id_buf[:S] = seg_u
        id_buf[S:padded] = -1
        tiles = self._seg_tiles_buf(padded, seg)
        tiles[:padded] = 0.0
        rows = np.searchsorted(seg_u, seg_of)
        tiles[rows, phys % seg] = base[phys]
        return (w_up, w_down, jnp.asarray(id_buf[:padded]),
                jnp.asarray(tiles[:padded]), w_gate)

    def _seg_ids_buf(self, padded: int) -> np.ndarray:
        buf = self._staging.get(("seg_ids",))
        if buf is None or buf.shape[0] < padded:
            size = max(padded, 2 * buf.shape[0] if buf is not None else padded)
            buf = np.empty((size,), dtype=np.int32)
            self._staging[("seg_ids",)] = buf
        return buf

    def _seg_tiles_buf(self, padded: int, seg: int) -> np.ndarray:
        buf = self._staging.get(("seg_tiles", seg))
        if buf is None or buf.shape[0] < padded:
            size = max(padded, 2 * buf.shape[0] if buf is not None else padded)
            buf = np.zeros((size, seg), dtype=np.float32)
            self._staging[("seg_tiles", seg)] = buf
        return buf

    @property
    def n_layers(self) -> int:
        return len(self.engines)

    def io_summary(self) -> dict:
        """Aggregate I/O metrics across layers.

        Ratio metrics (bandwidth, hit rate, mean run length) are computed
        from summed numerators and denominators — a mean of per-layer ratios
        would weight layers equally regardless of how much traffic each
        actually served."""
        tokens = [t for e in self.engines for t in e.history]
        io_s = sum(t.io.seconds for t in tokens)
        useful = sum(t.io.bytes_useful for t in tokens)
        hits = sum(e.cache.stats.hits for e in self.engines)
        accesses = sum(e.cache.stats.hits + e.cache.stats.misses
                       for e in self.engines)
        runs = (np.concatenate([np.asarray(t.run_lengths) for t in tokens])
                if tokens else np.zeros(0, dtype=np.int64))
        per_layer = [e.summary() for e in self.engines]
        out = {
            # resolved FFN path + why (the EngineConfig may have said "auto")
            "ffn_kernel": self.ffn_kernel,
            "ffn_kernel_decision": self.ffn_kernel_reason,
            "io_seconds_per_token": sum(s["io_seconds_per_token"]
                                        for s in per_layer),
            "mean_run_length": float(runs.mean()) if runs.size else 0.0,
            "effective_bandwidth": useful / io_s if io_s else 0.0,
            "cache_hit_rate": hits / accesses if accesses else 0.0,
            "ops_per_token": sum(s["ops_per_token"] for s in per_layer),
            # fault-tolerance counters: ALWAYS present (and exactly zero on
            # the clean path — the CI chaos job gates on that). retries /
            # corrupt_extents flow up from the stores' IOStats; degraded
            # steps / worker restarts come from prefetch supervision.
            "retries": sum(t.io.retries for t in tokens),
            "corrupt_extents": sum(t.io.corrupt_extents for t in tokens),
            "degraded_steps": sum(t.io.degraded_steps for t in tokens),
            "worker_restarts": self.worker_restarts,
        }
        # dual accounting: wall-clock of REAL file reads, when the stores
        # perform any (FileNeuronStore over a NeuronPack) — alongside, never
        # instead of, the modeled device seconds above
        meas_ops = sum(t.io.measured_ops for t in tokens)
        if meas_ops:
            n_tok = max(max(len(e.history) for e in self.engines), 1)
            out["measured_file_seconds_per_token"] = (
                sum(t.io.measured_seconds for t in tokens) / n_tok)
            out["measured_extents_total"] = meas_ops
            out["measured_bytes_total"] = sum(t.io.measured_bytes
                                              for t in tokens)
        return out

    def predict_step_io_seconds(self, unions) -> float:
        """Modeled flash seconds one decode step serving `unions` (a per-layer
        sequence of activated-neuron id arrays, one per layer engine) would
        cost right now. Pure: delegates to each engine's
        `predict_read_seconds` (cache peeked, not probed; adaptive thresholds
        read, not updated). The InferenceServer's flash-I/O-aware admission
        gate sums this with its compute estimate to decide whether admitting
        another request would blow active inter-token deadlines."""
        if len(unions) != len(self.engines):
            raise ValueError(f"expected {len(self.engines)} per-layer unions, "
                             f"got {len(unions)}")
        return sum(e.predict_read_seconds(u)
                   for e, u in zip(self.engines, unions))

    def reset_stats(self) -> None:
        for e in self.engines:
            e.reset_stats()
        self.topup_total = 0
        self.worker_restarts = 0
        self.degraded_steps = 0

    # -- lifecycle -----------------------------------------------------------
    def close(self) -> None:
        """Shut down the prefetch worker and close every layer store
        (releases `FileNeuronStore` fds + memmaps; the in-memory store's
        close is a no-op). Idempotent."""
        self.stop_prefetch()
        for e in self.engines:
            e.store.close()

    def __enter__(self) -> "OffloadedFFNRuntime":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def dense_ffn_layer_count(cfg: ModelConfig) -> int:
    """Number of dense-FFN layers the offload runtime serves (capture order:
    dense sublayers of the periodic stack prefix, times the group count)."""
    P = transformer.stack_period(cfg)
    return (cfg.n_layers // P) * sum(k == "dense"
                                     for k in cfg.ffn_kinds()[:P])


def validate_pack_for_model(pack, cfg: ModelConfig) -> None:
    """Submit-time geometry check: a NeuronPack can only serve a model whose
    dense-FFN layer count, neuron count (d_ff), and bundle width
    (n_mats * d_model) it matches. Packs built by the offline packer also
    record d_model / n_mats / activation in `meta`, which is checked when
    present — bundle_width alone cannot distinguish a [gate|up|down] silu
    bundle from an [up|down] relu bundle of 1.5x the d_model. Raises
    ValueError listing every mismatch."""
    n_mats = 3 if cfg.activation == "silu" else 2
    expected = dict(n_layers=dense_ffn_layer_count(cfg), n_neurons=cfg.d_ff,
                    bundle_width=n_mats * cfg.d_model)
    mismatches = [f"{k}: pack has {getattr(pack, k)}, model needs {v}"
                  for k, v in expected.items() if getattr(pack, k) != v]
    meta = getattr(pack, "meta", None) or {}
    mismatches += [
        f"meta.{k}: pack built for {meta[k]!r}, model is {v!r}"
        for k, v in (("d_model", cfg.d_model), ("n_mats", n_mats),
                     ("activation", cfg.activation))
        if k in meta and meta[k] != v]
    if mismatches:
        raise ValueError(
            f"NeuronPack {pack.path} does not fit this model config: "
            + "; ".join(mismatches))


# ---------------------------------------------------------------------------
# Serving engine
# ---------------------------------------------------------------------------

class ServingEngine:
    """One-shot batch front-end, kept as a thin compatibility wrapper.

    `serve(requests)` submits every request to a fresh slot-based
    `InferenceServer` (one slot per request) and drains it. For greedy
    same-length request groups the output is token-identical to the historic
    group-by-length lockstep path (rows are independent and sampling streams
    are per-request); what changed underneath: mixed-length requests now share
    one continuous batch, each request retires at its own `max_new_tokens` or
    stop token (freed rows leave the activation-mask unions, so finished
    requests stop incurring attributed flash I/O), and in prefetch mode ONE
    `PrefetchWorker` spans the whole call instead of one per group. New code
    should use `repro.serving.server.InferenceServer` directly — it adds
    mid-flight admission and streaming on the same machinery.
    """

    def __init__(self, model: Model, params: Any, max_len: int = 512,
                 swa: bool = False, mode: str = "resident",
                 offload: Optional[OffloadedFFNRuntime] = None,
                 scheduler: Optional[IOScheduler] = None,
                 oracle: bool = True,
                 prefetch: bool = False,
                 lookahead: Union[str, List[PredictorParams], None] = None,
                 pack_path: Optional[str] = None):
        """`prefetch=True` runs offload decode through the asynchronous
        layer-ahead pipeline: a background I/O worker serves layer k+1's
        engine step while the device computes layer k. `lookahead` picks the
        speculation source: a list of cross-layer predictor params (layer k's
        hidden -> layer k+1's mask), None to use the runtime's trained
        `lookahead` (falling back to "oracle"), or "oracle" — the exactness
        fallback where each layer's prefetch is issued with its TRUE mask
        (zero speculation depth, so no overlap, but the split-phase worker
        machinery is exercised bit-identically to serial).

        `pack_path` loads the offload runtime from an on-disk NeuronPack
        artifact (`OffloadedFFNRuntime.from_pack`, geometry-validated against
        the model config) instead of a caller-built runtime.
        """
        if mode not in ("resident", "offload"):
            raise ValueError(f"unknown serving mode {mode!r}")
        if pack_path is not None:
            if offload is not None:
                raise ValueError("pass either `offload` or `pack_path`, "
                                 "not both")
            if mode != "offload":
                raise ValueError("pack_path= requires mode='offload'")
            offload = OffloadedFFNRuntime.from_pack(model.cfg, pack_path)
        if mode == "offload":
            if offload is None:
                raise ValueError("mode='offload' needs an OffloadedFFNRuntime")
            cfg = model.cfg
            if cfg.is_encdec or cfg.family != "dense":
                raise ValueError("offload serving covers dense decoder-only archs")
        if isinstance(lookahead, str) and lookahead != "oracle":
            raise ValueError(f"unknown lookahead mode {lookahead!r}")
        self.model = model
        self.params = params
        self.max_len = max_len
        self.swa = swa
        self.mode = mode
        self.offload = offload
        self._owns_offload = pack_path is not None   # we built it: we close it
        self.oracle = oracle
        self.prefetch = prefetch
        self.lookahead = lookahead
        self.scheduler = scheduler or IOScheduler(overlap=True)
        self._decode = jax.jit(
            lambda p, t, pos, c: model.decode_step(p, t, pos, c))
        # shared across the per-serve() InferenceServers so admission prefill
        # compiles once per prompt length, not once per serve() call
        self._prefill = (None if model.cfg.is_encdec else jax.jit(
            lambda p, toks, c: model.prefill(p, {"tokens": toks}, c)))

    def close(self) -> None:
        """Release the offload runtime's resources; closes the layer stores
        only when this engine built the runtime itself (pack_path=)."""
        if self.offload is not None:
            if self._owns_offload:
                self.offload.close()
            else:
                self.offload.stop_prefetch()

    def __enter__(self) -> "ServingEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def serve(self, requests: List[Request], seed: int = 0) -> List[Result]:
        """Submit every request to a fresh InferenceServer (one decode slot
        per request) and drain it. Results come back in request order."""
        from repro.serving.server import InferenceServer
        if not requests:
            return []
        server = InferenceServer(
            self.model, self.params, max_slots=len(requests),
            max_len=self.max_len, swa=self.swa, mode=self.mode,
            offload=self.offload, scheduler=self.scheduler, oracle=self.oracle,
            prefetch=self.prefetch, lookahead=self.lookahead, seed=seed,
            decode_fn=self._decode if self.mode == "resident" else None,
            prefill_fn=self._prefill)
        try:
            handles = [server.submit(r) for r in requests]
            server.drain()
        finally:
            server.close()
        return [h.result for h in handles]


def build_offload_runtime(
    model: Model,
    params: Any,
    rng: Optional[np.random.Generator] = None,
    calib_batch: tuple = (8, 64),
    engine_cfg: Optional[EngineConfig] = None,
    device: Optional[UFSDevice] = None,
    use_placement: bool = True,
    train_lookahead: bool = False,
    lookahead_threshold: float = 0.35,
    lookahead_epochs: int = 4,
) -> OffloadedFFNRuntime:
    """Calibrate placements from a short random-token trace and pack the
    model's dense-FFN weights into flash bundles, one engine per dense layer.

    `use_placement=False` keeps the identity layout (the LLMFlash-style
    baseline arm of the benchmarks). `train_lookahead=True` additionally fits
    the cross-layer lookahead predictors (layer k's pre-FFN hidden -> layer
    k+1's mask) on the same calibration trace, enabling real speculation
    depth in the prefetch pipeline. Works for any stack period: layers are
    enumerated in the same (group, sublayer) order as `ffn_pre_act` capture.
    """
    from repro.core.coactivation import stats_from_masks
    from repro.core.placement import identity_placement, search_placement
    from repro.store.packer import extract_dense_ffn_bundles

    cfg = model.cfg
    if cfg.family != "dense" or cfg.is_encdec:
        raise ValueError("offload runtime covers dense decoder-only archs")
    rng = rng or np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, calib_batch), jnp.int32)
    out = model.forward(params, {"tokens": tokens}, capture_activations=True)
    bundles = extract_dense_ffn_bundles(cfg, params)
    placements = []
    for dense_idx in range(len(bundles)):
        if use_placement:
            masks = np.asarray(
                out["ffn_pre_act"][dense_idx] > 0).reshape(-1, cfg.d_ff)
            placements.append(search_placement(
                stats_from_masks(masks).distance_matrix(), mode="auto"))
        else:
            placements.append(identity_placement(cfg.d_ff))
    dense_idx = len(bundles)
    lookahead = None
    if train_lookahead and dense_idx > 1:
        hiddens = np.asarray(out["ffn_inputs"]).reshape(
            dense_idx, -1, cfg.d_model)
        masks = np.asarray(out["ffn_pre_act"] > 0).reshape(
            dense_idx, -1, cfg.d_ff)
        lookahead = train_lookahead_predictors(
            hiddens, masks, threshold=lookahead_threshold,
            epochs=lookahead_epochs)
    return OffloadedFFNRuntime(cfg, bundles, placements, device=device,
                               engine_cfg=engine_cfg, lookahead=lookahead,
                               lookahead_threshold=lookahead_threshold)
