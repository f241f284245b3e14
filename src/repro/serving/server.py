"""InferenceServer — slot-based continuous batching with an explicit request
lifecycle (QUEUED -> PREFILL -> DECODE -> FINISHED).

The one-shot `ServingEngine.serve()` bucketed requests by exact prompt length
and decoded each bucket in lockstep for max(max_new_tokens) steps: mixed-length
traffic never shared a batch, finished requests kept burning compute *and
attributed flash I/O*, and nothing could arrive mid-flight. This module is the
request-lifecycle runtime that replaces that barrier:

  * a fixed pool of `max_slots` KV-cache decode slots, each with its own
    sequence position (`models/transformer.py` decode steps take a per-slot
    position vector);
  * `submit(request) -> RequestHandle`, valid any time — including while other
    requests are decoding (mid-flight admission);
  * `step()` advances the server by one iteration: queued requests are
    admitted into free slots (each gets its own dense prefill, written into
    its slot — no group-by-length barrier), then one batched decode iteration
    runs over the active slots;
  * retirement on `max_new_tokens` ("length") or a stop token ("stop") frees
    the slot immediately: the retired row is dropped from every subsequent
    activation-mask union, so a finished request stops incurring flash I/O
    the step it finishes;
  * streaming via `submit(..., on_token=...)` callbacks or the pull-based
    `stream(handle)` iterator.

Offload mode rides the same loop: the [n_slots, n_neurons] activation-mask
matrix (inactive rows zeroed) feeds `OffloadEngine.step_masks`, per-uid I/O
attribution accumulates on each handle (summing exactly to the engines' merged
read time), and in prefetch mode ONE `PrefetchWorker` stays up across the
whole server run instead of starting/stopping per request group.

Sampling is grouping-invariant: request `uid`'s token `t` is sampled from the
stream `fold_in(fold_in(PRNGKey(seed), uid), t)`, so a request's tokens do not
depend on which batch, group, or slot it landed in — serving a request alone
and serving it inside any continuous batch produce identical output (greedy
AND temperature sampling), which is what the admission-order identity tests
assert.

Overload robustness (the serving layer's failure mode at scale is overload,
not bad reads — see ROADMAP open item 2):

  * bounded admission queue with explicit backpressure — `queue_limit` caps
    the number of QUEUED requests; a full queue sheds the worst
    strictly-lower-priority queued request in favor of the newcomer, or
    retires the newcomer itself with `finish_reason="rejected"`;
  * priority + earliest-deadline-first admission order: free slots go to the
    highest priority class first, earliest TTFT deadline within a class,
    submission order as the tie-break;
  * per-request SLOs on a monotonic clock (`Request.ttft_slo_s` /
    `Request.itl_slo_s`, with server-wide defaults): a queued request whose
    TTFT deadline passes, or an active request whose inter-token gap blows
    its deadline, is retired with `finish_reason="timeout"` — partial tokens
    preserved, slot freed immediately, per-uid io_seconds attribution still
    conserved (the orphan re-billing below never drops attributed reads);
  * flash-I/O-aware admission (offload mode): before admitting into a freed
    slot, the server predicts the NEXT step's cost — per-layer mask unions of
    the active batch (plus a frequency estimate for the candidate) priced on
    the calibrated `UFSDevice` via `OffloadEngine.predict_read_seconds`, plus
    the scheduler's recent compute-per-token — and leaves the candidate
    QUEUED when that prediction would blow an active request's inter-token
    deadline (`ServerStats.io_deferrals` counts these);
  * a stall watchdog: `stall_limit` consecutive `step()` calls with work
    pending but no progress (nothing admitted, emitted, or retired) raise
    `ServerStalledError` instead of spinning forever in `drain()`;
  * bounded memory: `finished_high_water` auto-releases the oldest delivered
    results past the mark (`ServerStats.results_released` counts them;
    caller-held handles stay valid).

Paged KV cache (`page_size=`/`num_pages=`, see `serving/paging.py`): instead
of one full-`max_len` contiguous KV region per slot, all KV memory lives in a
shared page arena and each request maps exactly the pages it has filled, so
the SAME memory budget serves several times the concurrency (a slot pins
ceil(len/page_size) pages, not max_len positions). Admission is gated by page
availability (`ServerStats.page_deferrals`) on top of the I/O gate; matched
prompt prefixes share pages copy-on-write (`prefix_hits`/`cow_copies`);
retirement on EVERY path — length/stop/timeout/error/rejected/preempted/abort
— releases the request's pages deterministically; and under page pressure
(`page_overcommit=True`) the decode-growth hook preempts the lowest-priority
active request (`finish_reason="preempted"`, partial tokens preserved) rather
than deadlocking. Decoded logits are bitwise identical to the contiguous
layout — the paged attend gathers pages into the same [B, S, KV, hd] view and
runs the identical causal GQA math.
"""
from __future__ import annotations

import dataclasses
import enum
import math
import time
from typing import Any, Callable, Dict, Iterator, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.pipeline import IOScheduler
from repro.core.predictor import PredictorParams, predict_mask
from repro.obs import get_metrics, get_tracer
from repro.obs import request_timeline as _build_request_timeline
from repro.utils import logger
from repro.models import transformer
from repro.models.layers import apply_norm, embed_tokens, unembed
from repro.models.model import Model
from repro.serving.engine import (OffloadedFFNRuntime, Request, Result,
                                  request_key)
from repro.serving.paging import PagePool, cdiv


class RequestState(enum.Enum):
    """Lifecycle of a request inside the server."""
    QUEUED = "queued"        # submitted, waiting for a free decode slot
    PREFILL = "prefill"      # admitted; its prompt is being prefilled
    DECODE = "decode"        # occupying a slot, generating tokens
    FINISHED = "finished"    # retired; `result` is populated


@dataclasses.dataclass
class RequestHandle:
    """Live view of one submitted request.

    `tokens` grows as the server steps (the streaming surface — read it, or
    register `on_token`, or drive `server.stream(handle)`); `result` is set at
    retirement. Timing fields accumulate while the request is in flight:
    `decode_seconds`/`overlapped_seconds` add each decode iteration's wall
    (every active request shares the batched step, same convention as the
    one-shot path), `io_seconds` adds this request's attributed share of the
    engines' flash reads.
    """
    request: Request
    state: RequestState = RequestState.QUEUED
    tokens: List[int] = dataclasses.field(default_factory=list)
    # "length" | "stop" | "error" | "timeout" | "rejected" | "preempted"
    # once FINISHED
    finish_reason: Optional[str] = None
    result: Optional[Result] = None
    error: Optional[BaseException] = None    # set iff finish_reason=="error"
    slot: Optional[int] = None
    on_token: Optional[Callable[[int, int], None]] = None   # (uid, token)
    prefill_seconds: float = 0.0
    decode_seconds: float = 0.0
    io_seconds: float = 0.0
    overlapped_seconds: float = 0.0
    # lifecycle stamps on the server's MONOTONIC clock (`time.monotonic` by
    # default) — deadline math and the load harness's TTFT/ITL numbers
    # survive wall-clock adjustments. `token_times` stamps every emitted
    # token (bounded by max_new_tokens), so inter-token gaps are exact.
    queued_at: float = 0.0
    admitted_at: Optional[float] = None
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    token_times: List[float] = dataclasses.field(default_factory=list)
    # resolved SLOs: request-level value if set, else the server default
    ttft_slo: Optional[float] = None
    itl_slo: Optional[float] = None
    _key: Any = None                         # fold_in(base_key, uid)
    _order: int = 0                          # submission order

    @property
    def uid(self) -> int:
        return self.request.uid

    @property
    def done(self) -> bool:
        return self.state is RequestState.FINISHED

    @property
    def ttft_deadline(self) -> Optional[float]:
        """Monotonic instant this request's first token is due, or None."""
        return None if self.ttft_slo is None else self.queued_at + self.ttft_slo


def _deadline_or_inf(handle: RequestHandle) -> float:
    """TTFT deadline for EDF ordering; no deadline sorts last (infinite
    slack)."""
    d = handle.ttft_deadline
    return math.inf if d is None else d


@dataclasses.dataclass
class ServerStats:
    """Aggregate counters over the server's lifetime (benchmark surface)."""
    n_slots: int = 0
    prefill_seconds: float = 0.0
    decode_seconds: float = 0.0       # wall of the batched decode iterations
    decode_steps: int = 0
    tokens_emitted: int = 0
    admitted: int = 0
    slot_steps_active: int = 0        # Σ over decode steps of active slots
    # -- overload-robustness counters ----------------------------------------
    retired: int = 0                  # every retirement, any finish_reason
    rejected: int = 0                 # newcomers bounced off a full queue
    shed: int = 0                     # queued requests evicted for higher prio
    timeouts: int = 0                 # TTFT or inter-token deadline blown
    io_deferrals: int = 0             # admissions deferred by the I/O gate
    results_released: int = 0         # finished handles auto-released past
    #                                   the finished_high_water mark
    peak_queue_depth: int = 0         # max QUEUED depth ever observed
    # -- paged-KV counters (mirrors of PagePoolStats; zero unless paged) ------
    pages_allocated: int = 0          # page allocations over the run
    pages_shared: int = 0             # pages mapped shared at admission
    prefix_hits: int = 0              # admissions that matched a shared prefix
    cow_copies: int = 0               # copy-on-write page copies
    peak_page_occupancy: int = 0      # max pages simultaneously referenced
    prefix_evictions: int = 0         # registry entries evicted under pressure
    page_deferrals: int = 0           # admissions deferred by the page gate
    preemptions: int = 0              # active requests retired for pages

    @property
    def occupancy(self) -> float:
        """Mean fraction of slots doing useful work per decode step."""
        denom = self.decode_steps * max(self.n_slots, 1)
        return self.slot_steps_active / denom if denom else 0.0


class ServerStalledError(RuntimeError):
    """`step()` made no progress — nothing admitted, emitted, or retired —
    for `stall_limit` consecutive iterations while work was pending. Raised
    instead of letting `drain()` spin forever; the message carries a queue /
    slot snapshot so the hang is diagnosable from the exception alone."""


class InferenceServer:
    """Slot-based continuous-batching front-end over one model.

    Same mode surface as `ServingEngine` (resident | offload, optional
    prefetch pipeline + lookahead source), but requests are individually
    admitted, decoded at per-slot positions, and individually retired.
    `ServingEngine.serve()` is the submit-all + drain compatibility wrapper
    over this class.

    Typical use::

        server = InferenceServer(model, params, max_slots=4, max_len=256)
        h = server.submit(Request(uid=0, prompt=prompt, max_new_tokens=32))
        for tok in server.stream(h):      # pumps server.step() as needed
            ...
        server.close()

    or batch-style: submit many, then `drain()`.
    """

    def __init__(self, model: Model, params: Any, *, max_slots: int = 4,
                 max_len: int = 512, swa: bool = False, mode: str = "resident",
                 offload: Optional[OffloadedFFNRuntime] = None,
                 scheduler: Optional[IOScheduler] = None,
                 oracle: bool = True, prefetch: bool = False,
                 lookahead: Union[str, List[PredictorParams], None] = None,
                 seed: int = 0, decode_fn=None, prefill_fn=None,
                 pack_path: Optional[str] = None,
                 queue_limit: Optional[int] = None,
                 ttft_slo_s: Optional[float] = None,
                 itl_slo_s: Optional[float] = None,
                 io_admission: bool = True, io_headroom: float = 1.0,
                 stall_limit: int = 256,
                 finished_high_water: Optional[int] = None,
                 clock: Optional[Callable[[], float]] = None,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 page_overcommit: bool = False):
        """`decode_fn` / `prefill_fn` let a long-lived caller (ServingEngine)
        share one jitted resident decode / admission prefill across servers;
        by default the server jits its own (prefill compiles once per prompt
        length — eager prefill cost hundreds of ms per admission at small
        geometries, which stalled co-batched requests' inter-token gaps).
        `lookahead` follows ServingEngine: predictor params, None (use
        the runtime's trained lookahead), or "oracle" (zero speculation
        depth — the exactness fallback). `pack_path` loads the offload
        runtime from an on-disk NeuronPack artifact
        (`OffloadedFFNRuntime.from_pack`, geometry-validated against the
        model config) instead of a caller-built runtime.

        Overload knobs: `queue_limit` bounds the admission queue (None =
        unbounded, the legacy behavior); `ttft_slo_s` / `itl_slo_s` are
        server-wide deadline defaults a request's own SLO fields override;
        `io_admission` arms the flash-I/O-aware admission gate (offload mode,
        inert unless some in-flight request has an inter-token SLO) with
        `io_headroom` scaling the budget (predicted step seconds must stay
        under headroom x the tightest active ITL deadline); `stall_limit`
        no-progress iterations raise `ServerStalledError`;
        `finished_high_water` bounds retained finished handles (oldest
        auto-released past the mark); `clock` injects a monotonic clock for
        deterministic deadline tests (default `time.monotonic`).

        Paged KV: set BOTH `page_size` and `num_pages` to replace the
        per-slot contiguous caches with a shared page arena
        (`serving/paging.py`) — decoder-only attention stacks, no `swa`.
        `page_overcommit=False` (strict) admits only requests whose
        worst-case page need is covered, so decode growth never runs dry;
        True gates on the immediate prompt need only, trading possible
        page-pressure preemption for higher admitted concurrency."""
        if mode not in ("resident", "offload"):
            raise ValueError(f"unknown serving mode {mode!r}")
        cfg = model.cfg
        if cfg.is_encdec:
            raise ValueError("InferenceServer covers decoder-only stacks")
        if pack_path is not None:
            if offload is not None:
                raise ValueError("pass either `offload` or `pack_path`, "
                                 "not both")
            if mode != "offload":
                raise ValueError("pack_path= requires mode='offload'")
            offload = OffloadedFFNRuntime.from_pack(cfg, pack_path)
        if mode == "offload":
            if offload is None:
                raise ValueError("mode='offload' needs an OffloadedFFNRuntime")
            if cfg.family != "dense":
                raise ValueError("offload serving covers dense decoder-only archs")
        if isinstance(lookahead, str) and lookahead != "oracle":
            raise ValueError(f"unknown lookahead mode {lookahead!r}")
        if max_slots < 1:
            raise ValueError("max_slots must be >= 1")
        if (page_size is None) != (num_pages is None):
            raise ValueError("pass both page_size and num_pages, or neither")
        if page_size is not None and swa:
            raise ValueError("paged KV cache does not combine with swa "
                             "(sliding-window rings are per-slot, not paged)")
        if queue_limit is not None and queue_limit < 1:
            raise ValueError("queue_limit must be >= 1 (or None = unbounded)")
        if stall_limit < 1:
            raise ValueError("stall_limit must be >= 1")
        self.model = model
        self.params = params
        self.cfg = cfg
        self.max_slots = max_slots
        self.max_len = max_len
        self.swa = swa
        self.mode = mode
        self.offload = offload
        self._owns_offload = pack_path is not None   # we built it: we close it
        self.oracle = oracle
        self.prefetch = prefetch
        self.lookahead = lookahead
        self.scheduler = scheduler or IOScheduler(overlap=True)
        self.stats = ServerStats(n_slots=max_slots)
        self.queue_limit = queue_limit
        self.default_ttft_slo = ttft_slo_s
        self.default_itl_slo = itl_slo_s
        self.io_admission = io_admission
        self.io_headroom = io_headroom
        self.stall_limit = stall_limit
        self.finished_high_water = finished_high_water
        self._clock = clock or time.monotonic
        self._stall_steps = 0
        self._base_key = jax.random.PRNGKey(seed)
        # jitted admission prefill (both modes; one compile per prompt length)
        self._prefill_fn = prefill_fn or jax.jit(
            lambda p, toks, c: model.prefill(p, {"tokens": toks}, c))
        self._queue: List[RequestHandle] = []
        self._handles: Dict[int, RequestHandle] = {}   # queued + in-flight
        self._finished: List[RequestHandle] = []
        self._n_submitted = 0
        # I/O-aware admission state (offload): last step's per-layer true
        # masks + an EMA of per-column activation frequency, the candidate
        # estimate for a not-yet-admitted request
        self._last_masks: List[Optional[np.ndarray]] = (
            [None] * offload.n_layers if mode == "offload" else [])
        self._col_freq: List[Optional[np.ndarray]] = list(self._last_masks)
        # slot pool: per-slot handle / next-decode position / last token
        self._slot_handle: List[Optional[RequestHandle]] = [None] * max_slots
        self._slot_pos = np.zeros(max_slots, dtype=np.int32)
        self._cur = np.zeros(max_slots, dtype=np.int32)
        # paged KV: the pool owns ALL KV memory; per-uid page tables map each
        # request onto exactly the pages it has filled
        self._pool: Optional[PagePool] = None
        self._tables: Dict[int, Any] = {}
        if page_size is not None:
            # PagePool/init_paged_stack_cache validate page geometry and
            # reject non-attention (SSM) sublayers with a ValueError — paged
            # serving never silently falls back
            self._pool = PagePool(
                cfg, num_pages=num_pages, page_size=page_size,
                max_len=max_len, overcommit=page_overcommit,
                layout="stacked" if mode == "resident" else "groups")
        if mode == "resident":
            if self._pool is not None:
                self._cache = None        # the arena replaces per-slot caches
                self._decode_fn = decode_fn or jax.jit(
                    lambda p, t, pos, c, pt: model.decode_step(
                        p, t, pos, c, page_tables=pt))
            else:
                self._cache = model.init_cache(max_slots, max_len, swa=swa)
                self._decode_fn = decode_fn or jax.jit(
                    lambda p, t, pos, c: model.decode_step(p, t, pos, c))
        else:
            self._cache_groups = (
                None if self._pool is not None else transformer.unstack_groups(
                    model.init_cache(max_slots, max_len, swa=swa), cfg))
            self._param_groups = transformer.unstack_groups(
                _offload_decode_stack(params["stack"], cfg, oracle), cfg)
            self._w_ups = (_oracle_w_ups(cfg, self._param_groups) if oracle
                           else None)
            if self._w_ups is not None and len(self._w_ups) != offload.n_layers:
                raise ValueError(
                    f"runtime has {offload.n_layers} layer engines, model has "
                    f"{len(self._w_ups)} dense FFN layers")
            # lookahead source resolution, identical to ServingEngine: params
            # > runtime-trained > "oracle" (depth 0)
            la = lookahead if not isinstance(lookahead, str) else None
            if la is None and lookahead is None:
                la = offload.lookahead
            if la is not None and la is not offload.lookahead:
                offload.lookahead = la
                offload._lookahead_np = None
            self._la_params = la
            self.scheduler.register_metrics()
            if prefetch and la is not None and \
                    cfg.activation not in ("relu", "relu2"):
                # speculative lookahead OVER-predicts by design; both FFN
                # paths (bundles and the fused segment kernel) evaluate the
                # whole SERVED union — speculated neurons included — which is
                # only exact when act(pre <= 0) == 0. Oracle lookahead
                # (la=None, zero speculation depth) stays exact for any
                # activation, on either kernel: the segment path masks
                # covered-but-not-served neurons in-kernel.
                raise ValueError(
                    f"prefetch with speculative lookahead is exact only for "
                    f"relu/relu2 activations, not {cfg.activation!r}; use "
                    f"lookahead='oracle' or serve serially")
        self._register_metrics()

    def _register_metrics(self) -> None:
        """Expose live server state through the global `MetricsRegistry` —
        gauge callables reading `ServerStats` and the queue/slot pool, so the
        registry and the legacy stats surface agree by construction. The
        registry keeps only the most recently constructed server per name
        (re-registration re-points the gauge)."""
        reg = get_metrics()
        reg.register_gauge("server.queue_depth", lambda: len(self._queue))
        reg.register_gauge("server.n_active", lambda: self.n_active)
        for field in ("tokens_emitted", "decode_steps", "admitted", "retired",
                      "rejected", "shed", "timeouts", "io_deferrals",
                      "page_deferrals", "preemptions", "prefill_seconds",
                      "decode_seconds"):
            reg.register_gauge(f"server.{field}",
                               lambda f=field: getattr(self.stats, f))
        reg.register_gauge("server.occupancy", lambda: self.stats.occupancy)
        self._step_hist = reg.histogram("server.step_seconds")

    def request_timeline(self, handle: RequestHandle) -> Dict[str, Any]:
        """Per-request timeline for SLO debugging: phase breakdown
        (queued/prefill/decode) from the handle's monotonic lifecycle stamps,
        per-token inter-token gaps, resolved SLOs and whether each was met,
        plus — when tracing is enabled — the trace spans tagged with this
        request's uid (`repro.obs.request_timeline`)."""
        return _build_request_timeline(handle)

    # -- submission ----------------------------------------------------------
    def submit(self, request: Request,
               on_token: Optional[Callable[[int, int], None]] = None
               ) -> RequestHandle:
        """Queue a request; valid any time, including mid-decode.

        Raises ValueError if the request cannot fit its slot: the prompt plus
        `max_new_tokens` must fit in `max_len` KV-cache positions (prompt
        tokens occupy [0, T); generated token i is decoded at position T+i-1,
        so the last decode writes position T + max_new_tokens - 2 < max_len).

        Backpressure: with `queue_limit` set and the queue full, either the
        worst STRICTLY-lower-priority queued request is shed in favor of this
        one (`stats.shed`), or — no such victim — this request is retired
        immediately with `finish_reason="rejected"` (`stats.rejected`). The
        returned handle is FINISHED in that case (`handle.done`, empty
        tokens, `result` populated); callers that must not drop work should
        check `handle.finish_reason` and re-submit later.
        """
        T = len(request.prompt)
        if T < 1:
            raise ValueError(f"request {request.uid}: empty prompt")
        if request.max_new_tokens < 1:
            raise ValueError(f"request {request.uid}: max_new_tokens must be >= 1")
        if T + request.max_new_tokens > self.max_len:
            raise ValueError(
                f"request {request.uid}: prompt ({T} tokens) + max_new_tokens "
                f"({request.max_new_tokens}) exceeds the server's max_len "
                f"({self.max_len}); shorten the request or raise max_len")
        if self._pool is not None:
            need = cdiv(T + request.max_new_tokens, self._pool.page_size)
            if need > self._pool.num_pages:
                raise ValueError(
                    f"request {request.uid}: prompt + max_new_tokens needs "
                    f"{need} pages of {self._pool.page_size}, but the pool "
                    f"has only {self._pool.num_pages}; shorten the request "
                    f"or grow the pool")
        if request.uid in self._handles:
            raise ValueError(f"duplicate request uid {request.uid}")
        handle = RequestHandle(request=request, on_token=on_token,
                               queued_at=self._clock(),
                               ttft_slo=(request.ttft_slo_s
                                         if request.ttft_slo_s is not None
                                         else self.default_ttft_slo),
                               itl_slo=(request.itl_slo_s
                                        if request.itl_slo_s is not None
                                        else self.default_itl_slo),
                               _key=request_key(self._base_key, request.uid),
                               _order=self._n_submitted)
        self._n_submitted += 1
        self._handles[request.uid] = handle
        if (self.queue_limit is not None
                and len(self._queue) >= self.queue_limit):
            victim = self._shed_victim(request.priority)
            if victim is None:
                logger.warning("queue full (%d): rejecting request %d "
                               "(priority %d)", len(self._queue),
                               request.uid, request.priority)
                get_tracer().instant("reject", uid=request.uid,
                                     priority=request.priority)
                self.stats.rejected += 1
                self._retire(handle, "rejected")
                return handle
            logger.warning("queue full (%d): shedding queued request %d "
                           "(priority %d) for request %d (priority %d)",
                           len(self._queue), victim.uid,
                           victim.request.priority, request.uid,
                           request.priority)
            get_tracer().instant("shed", uid=victim.uid,
                                 for_uid=request.uid)
            self._queue.remove(victim)
            self.stats.shed += 1
            self._retire(victim, "rejected")
        self._queue.append(handle)
        self.stats.peak_queue_depth = max(self.stats.peak_queue_depth,
                                          len(self._queue))
        return handle

    def _shed_victim(self, priority: int) -> Optional[RequestHandle]:
        """The queued request to shed for a priority-`priority` arrival: the
        lowest STRICTLY-lower priority class; within it, the latest TTFT
        deadline (most slack; no deadline = infinite slack), newest
        submission as the tie-break. None when nothing queued is strictly
        lower priority — the arrival is rejected instead."""
        cands = [h for h in self._queue if h.request.priority < priority]
        if not cands:
            return None
        return min(cands, key=lambda h: (h.request.priority,
                                         -_deadline_or_inf(h), -h._order))

    # -- introspection -------------------------------------------------------
    @property
    def has_work(self) -> bool:
        return bool(self._queue) or any(h is not None for h in self._slot_handle)

    @property
    def n_active(self) -> int:
        return sum(h is not None for h in self._slot_handle)

    @property
    def n_queued(self) -> int:
        return len(self._queue)

    def results(self) -> List[Result]:
        """Finished results the server still holds, in submission order."""
        return [h.result for h in sorted(self._finished,
                                         key=lambda h: h._order)]

    def release_finished(self) -> int:
        """Drop the server's references to finished requests (their handles
        stay valid for the caller). A long-lived server should call this
        periodically — or after consuming `drain()`/`results()` — so memory
        stays bounded by in-flight work, not by total requests served.
        Returns the number of handles released."""
        n = len(self._finished)
        self._finished.clear()
        return n

    # -- the serving loop ----------------------------------------------------
    def step(self) -> int:
        """Advance the server one iteration: admit queued requests into free
        slots (per-request prefill), then run one batched decode iteration
        over the active slots. Returns the number of tokens emitted.

        Error isolation, batch scope: an exception out of the shared decode
        computation (a flash read that exhausted its retries, a failing
        store) cannot be attributed to one request, so every active request
        is retired with `finish_reason="error"` and the exception attached
        — but the SERVER survives: queued and future submissions admit and
        decode normally. Per-request failures (sampling, a raising
        `on_token` callback, a failing prefill) are caught deeper down and
        retire only the offending request.

        SLO enforcement happens here, on the monotonic clock: blown
        inter-token deadlines retire active requests (slot freed before
        admission, so the slot is immediately reusable), blown TTFT
        deadlines retire queued requests before they waste a prefill, and
        admission itself runs in priority + earliest-deadline-first order,
        gated (offload mode) by the predicted flash cost of the grown batch.
        A `stall_limit` run of no-progress iterations with work pending
        raises `ServerStalledError`."""
        retired0, admitted0 = self.stats.retired, self.stats.admitted
        with get_tracer().span("step", queued=len(self._queue),
                               active=self.n_active):
            return self._step_inner(retired0, admitted0)

    def _step_inner(self, retired0: int, admitted0: int) -> int:
        emitted = 0
        now = self._clock()
        self._expire_active(now)
        self._expire_queued(now)
        while self._queue and None in self._slot_handle:
            cand = self._next_admission()
            if cand is None:               # an admission gate said "not yet"
                break
            got = self._admit(cand)
            if got is None:                # pool dry mid-admission: requeued
                break
            emitted += got
        if self._pool is not None:
            # make every active row's next position writable BEFORE the
            # batched decode: page-boundary growth, CoW at divergence points,
            # and — pool dry even after prefix eviction — preemption
            self._grow_page_tables()
        if any(h is not None for h in self._slot_handle):
            try:
                emitted += self._decode_iteration()
            except Exception as e:  # noqa: BLE001 — isolate, don't crash
                logger.warning("decode iteration failed (%r); retiring the "
                               "active batch with finish_reason='error'", e)
                for h in list(self._slot_handle):
                    if h is not None:
                        self._fail_request(h, e)
        if self._pool is not None:
            self._sync_page_stats()
        progress = (emitted + (self.stats.retired - retired0)
                    + (self.stats.admitted - admitted0))
        if progress == 0 and self.has_work:
            self._stall_steps += 1
            if self._stall_steps >= self.stall_limit:
                states = [h.state.value if h is not None else "free"
                          for h in self._slot_handle]
                raise ServerStalledError(
                    f"server made no progress for {self._stall_steps} "
                    f"consecutive step() iterations: {len(self._queue)} "
                    f"queued, {self.n_active} active, slots={states}, "
                    f"io_deferrals={self.stats.io_deferrals}; a queued "
                    f"request that can never admit (or an admission gate "
                    f"that never opens) would spin drain() forever")
        else:
            self._stall_steps = 0
        return emitted

    # -- SLO enforcement ------------------------------------------------------
    def _expire_queued(self, now: float) -> None:
        """Retire queued requests whose TTFT deadline already passed — they
        could not possibly meet it, so don't waste a prefill on them."""
        expired = [h for h in self._queue
                   if h.ttft_deadline is not None and now > h.ttft_deadline]
        for h in expired:
            self._queue.remove(h)
            self.stats.timeouts += 1
            logger.warning("request %d blew its TTFT deadline by %.3fs while "
                           "queued; retiring with finish_reason='timeout'",
                           h.uid, now - h.ttft_deadline)
            self._retire(h, "timeout")

    def _expire_active(self, now: float) -> None:
        """Retire active requests whose inter-token deadline has already
        passed since their last emitted token (the between-steps complement
        of the in-step gap check in `_emit`). Partial tokens are preserved;
        the slot frees immediately for the admission pass that follows."""
        for h in list(self._slot_handle):
            if h is None or h.itl_slo is None or not h.token_times:
                continue
            gap = now - h.token_times[-1]
            if gap > h.itl_slo:
                self.stats.timeouts += 1
                logger.warning("request %d blew its inter-token deadline "
                               "(%.3fs > %.3fs SLO) with %d tokens; retiring "
                               "with finish_reason='timeout'", h.uid, gap,
                               h.itl_slo, len(h.tokens))
                self._retire(h, "timeout")

    def _next_admission(self) -> Optional[RequestHandle]:
        """Pop the queued request to admit next — highest priority class
        first, earliest TTFT deadline within a class, submission order as the
        tie-break — unless the flash-I/O admission gate predicts the grown
        batch would blow an in-flight inter-token deadline, in which case the
        request stays QUEUED and None is returned (counted in
        `stats.io_deferrals`)."""
        if not self._queue:
            return None
        best = min(self._queue,
                   key=lambda h: (-h.request.priority, _deadline_or_inf(h),
                                  h._order))
        if self._page_defers(best):
            get_tracer().instant("defer", uid=best.uid, gate="page")
            self.stats.page_deferrals += 1
            return None
        if self._io_defers(best):
            get_tracer().instant("defer", uid=best.uid, gate="io")
            self.stats.io_deferrals += 1
            return None
        self._queue.remove(best)
        return best

    def _page_defers(self, candidate: RequestHandle) -> bool:
        """Page-availability admission gate (paged KV only): True when the
        pool cannot cover the candidate — its worst-case lifetime page need
        in strict mode, its immediate prompt need under `page_overcommit` —
        out of free + registry-evictable pages net of the commitments already
        promised to active requests and of the registry pages the candidate
        itself would pin. Never defers an empty batch: `submit` bounded the
        request to the pool, and with nothing active every non-free page is
        either registry-evictable or a prefix the candidate shares, so even
        after pinning its shares the candidate always fits."""
        if self._pool is None:
            return False
        if not any(h is not None for h in self._slot_handle):
            return False
        r = candidate.request
        plan = self._pool.plan_admit(np.asarray(r.prompt, dtype=np.int32),
                                     r.max_new_tokens)
        return not self._pool.can_admit(plan)

    def _io_defers(self, candidate: RequestHandle) -> bool:
        """Flash-I/O-aware admission gate: True when the UFS model predicts
        the next decode step WITH `candidate` admitted would exceed the
        tightest inter-token SLO among the active batch (+ the candidate),
        scaled by `io_headroom`. Never defers an empty batch (the candidate
        cannot blow anyone's deadline, and deferring would deadlock)."""
        if not self.io_admission or self.mode != "offload":
            return False
        if not any(h is not None for h in self._slot_handle):
            return False
        slos = [h.itl_slo for h in self._slot_handle
                if h is not None and h.itl_slo is not None]
        if candidate.itl_slo is not None:
            slos.append(candidate.itl_slo)
        if not slos:
            return False
        predicted = self._predict_step_seconds()
        if predicted is None:
            return False
        return predicted > self.io_headroom * min(slos)

    def _predict_step_seconds(self) -> Optional[float]:
        """Predicted seconds of the next decode step for the grown batch:
        per-layer extent reads priced on the calibrated `UFSDevice`
        (`OffloadEngine.predict_read_seconds` — cache peeked, thresholds
        read, nothing mutated) over the union of the active rows' last true
        masks plus a frequency-EMA estimate for the incoming request, plus
        the scheduler's recent compute share per token. None until a first
        decode step has recorded masks (cold server: admit freely)."""
        active = self._active_mask()
        unions: List[np.ndarray] = []
        for layer, masks in enumerate(self._last_masks):
            if masks is None:
                return None
            union = (masks & active[:, None]).any(axis=0)
            freq = self._col_freq[layer]
            if freq is not None:      # candidate estimate: typical-row mask
                union = union | (freq >= 0.5)
            unions.append(np.flatnonzero(union))
        io_s = self.offload.predict_step_io_seconds(unions)
        return io_s + self.scheduler.predicted_compute_seconds_per_token()

    def drain(self) -> List[Result]:
        """Step until every submitted request is finished."""
        while self.has_work:
            self.step()
        return self.results()

    def stream(self, handle: RequestHandle) -> Iterator[int]:
        """Yield `handle`'s tokens as they are generated, pumping `step()`
        whenever the caller is ahead of the server. Other in-flight requests
        advance too — they share the batched decode iterations."""
        i = 0
        while True:
            while i < len(handle.tokens):
                yield handle.tokens[i]
                i += 1
            if handle.done:
                return
            self.step()

    def abort(self, reason: Union[str, BaseException] = "aborted") -> int:
        """Retire every queued and in-flight request with
        `finish_reason="error"` (partial tokens preserved on each Result) —
        the graceful-interrupt path `launch/serve.py` uses on
        KeyboardInterrupt. Returns the number of requests retired; the
        server stays usable for new submissions."""
        exc = (reason if isinstance(reason, BaseException)
               else RuntimeError(str(reason)))
        n = 0
        while self._queue:
            self._fail_request(self._queue.pop(0), exc)
            n += 1
        for h in list(self._slot_handle):
            if h is not None:
                self._fail_request(h, exc)
                n += 1
        return n

    def close(self) -> None:
        """Release background resources: the prefetch worker always; the
        offload runtime's stores too when this server built the runtime
        itself (pack_path=). The server stays usable for inspection;
        further steps would restart the worker."""
        if self.mode == "offload" and self.offload is not None:
            if self._owns_offload:
                self.offload.close()
            else:
                self.offload.stop_prefetch()

    def __enter__(self) -> "InferenceServer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- admission / retirement ----------------------------------------------
    def _admit(self, handle: RequestHandle) -> Optional[int]:
        """Prefill one queued request into a free slot. Failure-isolated: an
        exception anywhere in admission (prefill, slot write, the first
        token's `on_token` callback) retires THIS request with
        `finish_reason="error"` and leaves the rest of the server intact.

        Returns the number of tokens emitted (0 or 1), or None when the page
        pool ran dry mid-admission: the request goes BACK to the queue
        (counted as a page deferral, nothing to unwind — the table is built
        before the prefill), and the caller stops admitting this step."""
        slot = self._slot_handle.index(None)
        r = handle.request
        table = prompt_np = None
        if self._pool is not None:
            prompt_np = np.asarray(r.prompt, dtype=np.int32)
            table, _ = self._pool.admit(prompt_np, r.max_new_tokens,
                                        uid=r.uid)
            if table is None:
                # the gate prices pinned shares, so this should not happen —
                # but a dry pool defers rather than killing the request (the
                # stall watchdog catches a gate that never opens)
                logger.warning("page pool dry while admitting request %d; "
                               "deferring it back to the queue", r.uid)
                self.stats.page_deferrals += 1
                self._queue.append(handle)
                return None
            self._tables[r.uid] = table
        handle.state = RequestState.PREFILL
        handle.slot = slot
        handle.admitted_at = self._clock()
        try:
            T = len(r.prompt)
            prompt = jnp.asarray(np.asarray(r.prompt, dtype=np.int32)[None])
            tr = get_tracer()
            t0u = tr.now()
            with tr.span("prefill", uid=r.uid, prompt_len=T, slot=slot):
                t0 = time.perf_counter()
                small = self.model.init_cache(1, self.max_len, swa=self.swa)
                logits, small = self._prefill_fn(self.params, prompt, small)
                row = np.asarray(logits[0, -1], dtype=np.float32)  # the sync
                handle.prefill_seconds = time.perf_counter() - t0
            t1u = tr.now()
            # mirrored onto the request's own lane, so one Perfetto row shows
            # the request's whole life (prefill + every decode span)
            tr.complete("prefill", t0u, t1u, track=f"req {r.uid}", uid=r.uid)
            self.stats.prefill_seconds += handle.prefill_seconds
            self.stats.admitted += 1
            with tr.span("page_write"):
                if self._pool is not None:
                    # the table was registered in _tables before the prefill,
                    # so any failure below releases the pages via _retire
                    self._pool.write_prompt(table, small)
                    self._pool.register_prefixes(prompt_np, table)
                else:
                    self._write_slot(slot, small)
            self._slot_handle[slot] = handle
            self._slot_pos[slot] = T
            handle.state = RequestState.DECODE
            tok = self._sample_row(handle, row)
            self._cur[slot] = tok
            self._emit(handle, tok)
            # the first token comes out of the prefill forward pass; give it
            # its decode span too so "one decode span per emitted token"
            # holds exactly over a whole run
            t2u = tr.now()
            tr.complete("decode", t1u, t2u, track=f"req {r.uid}", uid=r.uid,
                        tok=tok, n_tokens=1, from_prefill=True)
        except Exception as e:  # noqa: BLE001 — per-request isolation
            self._fail_request(handle, e)
            return 0
        return 1

    def _write_slot(self, slot: int, small_cache: Any) -> None:
        """Copy a freshly prefilled B=1 cache into row `slot` of the pool.

        Stale KV beyond the new prompt is harmless: decode writes a position's
        KV before attending to it, and causal masking hides everything past
        the current position."""
        if self.mode == "resident":
            # stacked leaves are [G, B, ...]: batch is axis 1
            self._cache = jax.tree_util.tree_map(
                lambda big, s: big.at[:, slot].set(s[:, 0]),
                self._cache, small_cache)
        else:
            small_groups = transformer.unstack_groups(small_cache, self.cfg)
            self._cache_groups = [
                jax.tree_util.tree_map(lambda big, s: big.at[slot].set(s[0]),
                                       big_g, small_g)
                for big_g, small_g in zip(self._cache_groups, small_groups)]

    def _emit(self, handle: RequestHandle, tok: int) -> None:
        now = self._clock()
        handle.tokens.append(tok)
        handle.token_times.append(now)
        if handle.first_token_at is None:
            handle.first_token_at = now
        self.stats.tokens_emitted += 1
        if handle.on_token is not None:
            handle.on_token(handle.uid, tok)
        if tok in handle.request.stop_tokens:
            self._retire(handle, "stop")
        elif len(handle.tokens) >= handle.request.max_new_tokens:
            self._retire(handle, "length")
        elif (handle.itl_slo is not None and len(handle.token_times) >= 2
              and now - handle.token_times[-2] > handle.itl_slo):
            # the gap to the PREVIOUS token blew the inter-token deadline
            # (completion reasons above take precedence); the late token is
            # preserved — partial output, slot freed immediately
            self.stats.timeouts += 1
            logger.warning("request %d blew its inter-token deadline "
                           "(%.3fs > %.3fs SLO) at token %d; retiring with "
                           "finish_reason='timeout'", handle.uid,
                           now - handle.token_times[-2], handle.itl_slo,
                           len(handle.tokens))
            self._retire(handle, "timeout")

    def _retire(self, handle: RequestHandle, reason: str,
                error: Optional[BaseException] = None) -> None:
        get_tracer().instant("retire", uid=handle.uid, finish_reason=reason,
                             n_tokens=len(handle.tokens))
        handle.finish_reason = reason
        handle.error = error
        handle.state = RequestState.FINISHED
        handle.result = Result(
            uid=handle.uid, tokens=list(handle.tokens),
            prefill_seconds=handle.prefill_seconds,
            decode_seconds=handle.decode_seconds,
            io_seconds=handle.io_seconds,
            overlapped_seconds=handle.overlapped_seconds,
            finish_reason=reason, error=error)
        handle.finished_at = self._clock()
        if handle.slot is not None:                 # error-retired requests
            self._slot_handle[handle.slot] = None   # may never have held a
            handle.slot = None                      # slot; freed rows leave
        self._handles.pop(handle.uid, None)         # every future mask union
        if self._pool is not None:
            # deterministic page reclamation on EVERY retirement path —
            # length/stop/timeout/error/rejected/preempted/abort all land here
            table = self._tables.pop(handle.uid, None)
            if table is not None:
                self._pool.release(table)
        self._finished.append(handle)
        self.stats.retired += 1
        hw = self.finished_high_water
        if hw is not None and len(self._finished) > hw:
            # bounded memory: auto-release the oldest delivered results past
            # the high-water mark (caller-held handles stay valid; only the
            # server's own references are dropped)
            drop = len(self._finished) - hw
            del self._finished[:drop]
            self.stats.results_released += drop

    def _fail_request(self, handle: RequestHandle,
                      exc: BaseException) -> None:
        """Retire one request with `finish_reason="error"`: partial tokens
        stay on the Result, the exception is attached, the slot (if any) is
        freed, and everything else in the batch keeps decoding."""
        if handle.done:
            return
        logger.warning("request %d failed (%r); retiring with "
                       "finish_reason='error'", handle.uid, exc)
        self._retire(handle, "error", error=exc)

    # -- paged-KV growth / preemption -----------------------------------------
    def _grow_page_tables(self) -> None:
        """Pre-decode growth pass: every active row's next write position
        gets a resident, privately-owned page (boundary alloc / CoW). In
        strict admission mode the pool can never be dry here — admission
        reserved every request's worst case. Under `page_overcommit` a dry
        pool preempts: the registry is already drained by the allocator, so
        the lowest-priority active request (latest deadline, newest — the
        `_shed_victim` key) retires with `finish_reason="preempted"`, its
        partial tokens intact and its pages released, and the needer
        retries. The needer can be its own victim."""
        for slot in range(self.max_slots):
            while True:
                h = self._slot_handle[slot]
                if h is None:
                    break
                table = self._tables.get(h.uid)
                if table is None or \
                        self._pool.prepare_append(table,
                                                  int(self._slot_pos[slot])):
                    break
                victim = min(
                    (a for a in self._slot_handle if a is not None),
                    key=lambda a: (a.request.priority, -_deadline_or_inf(a),
                                   -a._order))
                self.stats.preemptions += 1
                get_tracer().instant("preempt", uid=victim.uid,
                                     for_uid=h.uid,
                                     priority=victim.request.priority)
                logger.warning(
                    "page pool dry growing request %d (pos %d): preempting "
                    "request %d (priority %d, %d tokens) with "
                    "finish_reason='preempted'", h.uid,
                    int(self._slot_pos[slot]), victim.uid,
                    victim.request.priority, len(victim.tokens))
                self._retire(victim, "preempted")

    def _page_tables_np(self) -> np.ndarray:
        """[max_slots, max_pages] physical-page array for the decode step;
        free slots (and every unallocated logical page) point at the null
        page, so their garbage writes cannot touch a live page."""
        pool = self._pool
        pt = np.full((self.max_slots, pool.max_pages_per_seq),
                     pool.null_page, dtype=np.int32)
        for slot, h in enumerate(self._slot_handle):
            if h is not None:
                table = self._tables.get(h.uid)
                if table is not None:
                    pool.page_table_row(table, pt[slot])
        return pt

    def _sync_page_stats(self) -> None:
        ps = self._pool.stats
        s = self.stats
        s.pages_allocated = ps.pages_allocated
        s.pages_shared = ps.pages_shared
        s.prefix_hits = ps.prefix_hits
        s.cow_copies = ps.cow_copies
        s.peak_page_occupancy = ps.peak_page_occupancy
        s.prefix_evictions = ps.prefix_evictions

    def page_summary(self) -> Optional[Dict[str, Any]]:
        """Pool configuration + lifetime counters (io_summary-style surface;
        None when the server is not paged)."""
        if self._pool is None:
            return None
        out = self._pool.summary()
        out["page_deferrals"] = self.stats.page_deferrals
        out["preemptions"] = self.stats.preemptions
        return out

    # -- sampling (per-request streams) ---------------------------------------
    def _sample_row(self, handle: RequestHandle, row: np.ndarray) -> int:
        """Sample token t = len(handle.tokens) of this request from its own
        stream. Row-wise, so the value is independent of batch composition."""
        temp = handle.request.temperature
        if temp <= 0:
            return int(np.argmax(row))
        key = jax.random.fold_in(handle._key, len(handle.tokens))
        return int(jax.random.categorical(
            key, jnp.asarray(row, jnp.float32) / temp))

    # -- decode ---------------------------------------------------------------
    def _active_mask(self) -> np.ndarray:
        return np.array([h is not None for h in self._slot_handle], dtype=bool)

    def _decode_iteration(self) -> int:
        active = self._active_mask()
        tr = get_tracer()
        t0u = tr.now()
        with tr.span("decode_step", batch=int(active.sum()),
                     step=self.stats.decode_steps):
            if self.mode == "resident":
                logits_rows, token_wall, req_io, over = self._decode_resident()
            else:
                logits_rows, token_wall, req_io, over = \
                    self._decode_offload(active)
        t1u = tr.now()
        self._step_hist.observe(token_wall)
        self.stats.decode_seconds += token_wall
        self.stats.decode_steps += 1
        self.stats.slot_steps_active += int(active.sum())
        # conservation: I/O the engine attributed to now-inactive rows (pure
        # over-speculation splits evenly over ALL rows) is re-billed evenly to
        # the active requests, so Σ per-request io == Σ engine merged reads
        orphan = float(req_io[~active].sum())
        share = orphan / max(int(active.sum()), 1)
        emitted = 0
        for slot in np.flatnonzero(active):
            handle = self._slot_handle[slot]
            handle.decode_seconds += token_wall
            handle.overlapped_seconds += over
            handle.io_seconds += float(req_io[slot]) + share
            # per-request isolation: sampling or a raising on_token callback
            # retires only THIS request; the loop continues for the rest of
            # the batch (the shared compute above already succeeded).
            try:
                tok = self._sample_row(handle, logits_rows[slot])
                self._slot_pos[slot] += 1
                self._cur[slot] = tok
                self._emit(handle, tok)             # may free the slot
                emitted += 1
                # one decode span per emitted token on the request's own
                # lane; the duration is the shared batched step's wall
                tr.complete("decode", t0u, t1u, track=f"req {handle.uid}",
                            uid=handle.uid, tok=tok,
                            n_tokens=len(handle.tokens))
            except Exception as e:  # noqa: BLE001
                self._fail_request(handle, e)
        return emitted

    def _decode_resident(self):
        t0 = time.perf_counter()
        if self._pool is not None:
            logits, self._pool.cache = self._decode_fn(
                self.params, jnp.asarray(self._cur)[:, None],
                jnp.asarray(self._slot_pos), self._pool.cache,
                jnp.asarray(self._page_tables_np()))
        else:
            logits, self._cache = self._decode_fn(
                self.params, jnp.asarray(self._cur)[:, None],
                jnp.asarray(self._slot_pos), self._cache)
        with get_tracer().span("sync"):
            rows = np.asarray(logits[:, 0], dtype=np.float32)
        wall = time.perf_counter() - t0
        return rows, wall, np.zeros(self.max_slots), 0.0

    # -- offload decode: masks -> batched engine step -> sparse FFN ----------
    def _true_masks(self, dense_idx: int, h2: jnp.ndarray,
                    active: np.ndarray) -> np.ndarray:
        """[n_slots, n_neurons] activation masks for one layer: the exact ReLU
        oracle (or trained predictor), with retired/free rows zeroed so they
        leave the union — a finished request incurs no further I/O."""
        if self._w_ups is not None:
            masks = h2 @ self._w_ups[dense_idx] > 0
        else:
            assert self.offload.predictors is not None, \
                "oracle=False needs runtime predictors"
            masks = predict_mask(self.offload.predictors[dense_idx], h2)
        with get_tracer().span("sync"):
            masks = np.asarray(masks)
        masks = masks & active[:, None]
        # feed the admission predictor: this layer's last true masks, plus an
        # EMA of per-column activation frequency over the active rows (the
        # candidate-row estimate for a not-yet-admitted request)
        self._last_masks[dense_idx] = masks
        if self.io_admission and active.any():
            col = masks[active].mean(axis=0)
            prev = self._col_freq[dense_idx]
            self._col_freq[dense_idx] = (col if prev is None
                                         else 0.8 * prev + 0.2 * col)
        return masks

    def _decode_offload(self, active: np.ndarray):
        cfg = self.cfg
        runtime = self.offload
        n_slots = self.max_slots
        n_layers = runtime.n_layers
        req_io = np.zeros(n_slots)
        tr = get_tracer()
        if self.prefetch and not runtime.prefetch_active:
            runtime.start_prefetch()        # one worker for the whole run
        la_params = self._la_params if self.prefetch else None

        # Sync-free serial path: XLA dispatch runs ahead across layers while
        # the engine serves each layer's masks host-side; one end-of-token
        # sync, apportioned across stages by FLOPs (see ServingEngine notes).
        def override(dense_idx: int, normed2: jnp.ndarray) -> jnp.ndarray:
            h2 = normed2[:, 0]
            masks = self._true_masks(dense_idx, h2, active)
            y, res = runtime.ffn_apply_batch(dense_idx, h2, masks)
            with tr.span("stage_accounting"):
                flops = (2.0 * n_slots * res.merged.n_activated
                         * runtime.n_mats * cfg.d_model)
                self.scheduler.record_stage(dense_idx,
                                            io_seconds=res.merged.io.seconds,
                                            flops=flops)
                np.add(req_io, res.req_io_seconds, out=req_io)
            return y[:, None]

        # Pipelined path: submit layer k+1's speculated prefetch, then
        # complete layer k against its true mask (top-up for mis-predictions).
        def override_prefetch(dense_idx: int, normed2: jnp.ndarray) -> jnp.ndarray:
            h2 = normed2[:, 0]
            masks_true = self._true_masks(dense_idx, h2, active)
            if dense_idx == 0 or la_params is None:
                runtime.begin_layer(dense_idx, masks_true)   # depth 0
            if la_params is not None and dense_idx + 1 < n_layers:
                with tr.span("sync"):
                    h_np = np.asarray(h2)
                spec = runtime.predict_lookahead(dense_idx, h_np)
                spec = spec & active[:, None]
                runtime.begin_layer(dense_idx + 1, spec)
            y, res, meas = runtime.complete_layer(dense_idx, h2, masks_true)
            with tr.span("stage_accounting"):
                flops = (2.0 * n_slots * res.merged.n_activated
                         * runtime.n_mats * cfg.d_model)
                self.scheduler.record_stage(dense_idx,
                                            io_seconds=res.merged.io.seconds,
                                            flops=flops, measured=meas)
                np.add(req_io, res.req_io_seconds, out=req_io)
            return y[:, None]

        ffn_override = override_prefetch if self.prefetch else override
        t0 = time.perf_counter()
        with tr.span("embed"):
            x = embed_tokens(self.params["embed"],
                             jnp.asarray(self._cur)[:, None], cfg)
        self.scheduler.begin_token()
        paged = self._pool is not None
        cache_groups = self._pool.cache_groups if paged else self._cache_groups
        h, cache_groups = transformer.stack_decode_step_layerwise(
            self._param_groups, x, jnp.asarray(self._slot_pos),
            cache_groups, cfg, ffn_override=ffn_override,
            page_tables=(jnp.asarray(self._page_tables_np()) if paged
                         else None))
        if paged:
            self._pool.cache_groups = cache_groups
        else:
            self._cache_groups = cache_groups
        with tr.span("unembed"):
            h = apply_norm(self.params["final_norm"], h, cfg)
            logits = unembed(self.params["embed"], h, cfg)
        with tr.span("sync"):                               # ONE per token
            rows = np.asarray(logits[:, 0], dtype=np.float32)
        token_wall = time.perf_counter() - t0
        timing = self.scheduler.end_token(
            compute_seconds=token_wall,
            wall_seconds=token_wall if self.prefetch else None)
        over = (timing.measured_wall_seconds if self.prefetch
                else timing.overlapped_seconds)
        return rows, token_wall, req_io, over


def _offload_decode_stack(stack: Any, cfg: ModelConfig, oracle: bool) -> Any:
    """The part of the stacked params the offload decode reads. The runtime
    serves every dense FFN, so of each dense FFN only `w_up` stays, and only
    for the oracle's masks: the per-group copy then holds no weight it never
    reads (1.6 GB of HBM at OPT-1.3B)."""
    ffns = cfg.ffn_kinds()
    stack = dict(stack)
    for j in range(transformer.stack_period(cfg)):
        if ffns[j] == "dense":
            sub = stack[f"sub_{j}"]
            stack[f"sub_{j}"] = {
                **sub, "ffn": {"w_up": sub["ffn"]["w_up"]} if oracle else {}}
    return stack


def _oracle_w_ups(cfg: ModelConfig,
                  param_groups: List[Any]) -> List[jnp.ndarray]:
    """Resident w_up handles per dense layer, in capture order — the exact
    ReLU support oracle the predictor approximates. The handles are the
    per-group arrays the server already holds, so the oracle costs no second
    copy of the weights. The simulated flash still pays for every neuron the
    mask selects."""
    ffns = cfg.ffn_kinds()
    return [group[f"sub_{j}"]["ffn"]["w_up"]
            for group in param_groups
            for j in range(transformer.stack_period(cfg))
            if ffns[j] == "dense"]
