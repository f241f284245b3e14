"""The benchmark harness: one cell of `BENCHMARK.json`, one seed, one run.

Everything that belongs to one configuration, traffic mix, per-layer metric
or cell's limits is a file of its own, found by name:

  BENCHMARK.json                  cells, metrics, bounds
  bench/configs/<config>.json     published sizes, source, reduced keys,
                                  assumptions, and the program's `program`
                                  configuration
  bench/reference/<module>.py     the plain reference a config names:
                                  `Dims.from_config`, `make_params`,
                                  `forward_logits`
  bench/traffic/<traffic>.json    the mix the one generator reads
  bench/metrics/<metric>.py       `read(run)` -> value or None
  bench/limits/<cell>.json        the limits `correct` is decided by

A run: make the weights from the seed, build the server the mix drives, warm
up every shape the mix uses, measure for `seconds`, then check what the timed
path served against the plain reference, and print one JSON line.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
GOOD_FINISH = ("length", "stop")
WARM_UP_UIDS = 10**9          # request ids of the warm-up, apart from traffic


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


# -- the spec --------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    mix: Dict[str, Any]
    limits: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    root: Path

    @property
    def bench(self) -> Path:
        return self.root / "bench"


def _read_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]

    def mine(metric: Dict[str, Any]) -> bool:
        return name in metric.get("workloads", [name])
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_read_json(root / cfg_entry["file"]),
        mix=_read_json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        limits=_read_json(root / "bench" / "limits" / f"{name}.json"),
        end_to_end=[m for m in spec["end_to_end"] if mine(m)],
        per_layer=[m for m in spec["per_layer"] if mine(m)],
        root=root)


def load_module(path: Path):
    """Import a file by path (metric files carry dots in their names), once
    per process."""
    name = "bench_file_" + "".join(c if c.isalnum() else "_"
                                   for c in str(path.resolve()))
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def reference_module(cell: Cell):
    return load_module(cell.bench / "reference" / f"{cell.config['reference']}.py")


# -- compile accounting ------------------------------------------------------

class CompileCounter:
    """Counts programs compiled, or loaded from the persistent cache, from
    JAX's monitoring events. Inside the window it should stay at zero."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_hits")

    def __init__(self) -> None:
        from jax import monitoring
        self.n = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name: str, _secs: float, **_kw) -> None:
        if name == self.EVENTS[0]:
            self.n += 1

    def _on_event(self, name: str, **_kw) -> None:
        if name == self.EVENTS[1]:
            self.n += 1


def enable_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    holding every program, so only a cell's first run compiles."""
    import jax
    path = str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# -- the program under test ----------------------------------------------------

def program_config(cfg: Dict[str, Any]):
    """The program's ModelConfig for a bench config file: the program's own
    entry for `program.arch`, with every field the `program` block states."""
    from repro.configs import ALL_CONFIGS
    fields = dict(cfg["program"])
    return dataclasses.replace(ALL_CONFIGS[fields.pop("arch")], **fields)


def check_layout(model, params) -> None:
    """The bench makes the weights; they must be exactly what the program's
    own initialiser would lay out."""
    import jax
    want = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    got = jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                                 params)
    if jax.tree_util.tree_structure(want) != jax.tree_util.tree_structure(got) \
            or jax.tree_util.tree_leaves(want) != jax.tree_util.tree_leaves(got):
        raise RuntimeError("the program's parameter layout is not the one the "
                           "benchmark makes")


@dataclasses.dataclass
class Served:
    """What a request went through: its prompt, the tokens served, and the
    stamps the server put on it."""
    handle: Any
    prompt: np.ndarray
    due: Optional[float]            # open loop: when it was due (monotonic)


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read about one run."""
    cell: Cell
    dims: Any
    peak: Any
    t0: float
    t1: float
    setup_s: float
    served: List[Served]            # requests in the window
    window_tokens: int
    gaps: List[float]
    n_mats: int
    compiles_in_window: int
    memory_peak_bytes: int
    extents: Optional[Tuple[int, int]] = None       # (extents, steps)
    exposed: Optional[Tuple[float, int]] = None     # (seconds, steps)
    work: Any = None                                # spans.WorkLog
    trace: Any = None                               # trace.Reduction

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0


def _build_server(cell: Cell, model, params, seed: int):
    from repro.serving.server import InferenceServer
    from bench import traffic
    mix = cell.mix
    page = int(mix["page_size"])
    slots = int(mix["slots"])
    max_len = max(traffic.ladder(mix)) + traffic.max_new_tokens(mix) + 8
    pages = traffic.pages_needed(mix, page, slots)
    kw = dict(max_slots=slots, max_len=max_len, seed=seed % 2**31,
              page_size=page, num_pages=pages)
    if mix["serve_mode"] == "resident":
        return InferenceServer(model, params, mode="resident", **kw), None
    from repro.core import EngineConfig, IOScheduler
    from repro.serving.engine import build_offload_runtime
    t = time.monotonic()
    runtime = build_offload_runtime(
        model, params, rng=np.random.default_rng(seed % 2**63),
        engine_cfg=EngineConfig(), use_placement=True, train_lookahead=True)
    log(f"offline stage (calibration, placement, lookahead): "
        f"{time.monotonic() - t:.3f} s; ffn path {runtime.ffn_kernel}")
    server = InferenceServer(model, params, mode="offload", offload=runtime,
                             scheduler=IOScheduler(overlap=True),
                             prefetch=True, **kw)
    return server, runtime


def _request(uid: int, prompt: np.ndarray, new_tokens: int):
    from repro.serving.engine import Request
    return Request(uid=uid, prompt=prompt, max_new_tokens=new_tokens)


def _warm_up(cell: Cell, server, runtime, seed: int, vocab: int) -> None:
    """Compile everything the window will run: admission at every prompt
    length of the mix and, for the offload decode, the fused kernel at every
    segment-count bucket and a few decode steps (done by the caller, which
    fills the slots)."""
    import jax
    import jax.numpy as jnp
    from bench import traffic
    uid = WARM_UP_UIDS
    for _ in range(2):
        for T in traffic.ladder(cell.mix):
            server.submit(_request(uid, traffic.prompt_tokens(seed, uid, T,
                                                              vocab), 1))
            uid += 1
            server.drain()
    server.release_finished()
    if runtime is None:
        return
    from repro.kernels import ops
    seg = runtime.engine_cfg.kernel_seg_size
    bucket = type(runtime).SEG_ID_BUCKET
    n_seg = -(-server.cfg.d_ff // seg)
    h = jnp.zeros((server.max_slots, server.cfg.d_model), jnp.float32)
    first = runtime.engines[0].placement.placement
    for S in range(bucket, -(-n_seg // bucket) * bucket + 1, bucket):
        ids = first[np.arange(min(S, n_seg)) * seg]
        out = ops.sparse_ffn_segments_fused(
            h, *runtime.segment_kernel_inputs(0, ids), seg_size=seg,
            activation=server.cfg.activation)
        jax.block_until_ready(out)


class ClosedLoop:
    """Each of `clients` clients sends its next request when its last one
    finished. `prepare` fills every slot and runs a few decode steps before
    the window opens."""

    def __init__(self, cell, server, plan, seed, vocab):
        self.server, self.plan, self.seed, self.vocab = server, plan, seed, vocab
        self.clients = int(cell.mix["clients"])
        self.sent = 0
        self.served: List[Served] = []

    def _send(self) -> Served:
        from bench import traffic
        i, n = self.sent, len(self.plan.prompt_lens)
        self.sent += 1
        p = traffic.prompt_tokens(self.seed, i, self.plan.prompt_lens[i % n],
                                  self.vocab)
        s = Served(self.server.submit(_request(i, p, self.plan.new_tokens[i % n])),
                   p, None)
        self.served.append(s)
        return s

    def prepare(self) -> None:
        self.live = [self._send() for _ in range(self.clients)]
        while self.server.n_queued or self.server.stats.decode_steps < 3:
            self.server.step()
            bad = [s.handle for s in self.live if s.handle.done]
            if bad:
                raise RuntimeError(
                    f"request {bad[0].uid} finished {bad[0].finish_reason!r} "
                    f"before the window: {bad[0].error!r}")

    def run(self, seconds: float, clock) -> Tuple[float, float]:
        t0 = clock()
        while clock() < t0 + seconds:
            self.server.step()
            for k, s in enumerate(self.live):
                if s.handle.done:
                    self.live[k] = self._send()
        return t0, clock()


class OpenLoop:
    """Requests are submitted when due, whatever the server is doing; the
    window runs until every request due in it has finished."""

    def __init__(self, cell, server, plan, seed, vocab):
        self.server, self.plan, self.seed, self.vocab = server, plan, seed, vocab
        self.served: List[Served] = []

    def prepare(self) -> None:
        pass

    def run(self, seconds: float, clock) -> Tuple[float, float]:
        from bench import traffic
        server, plan = self.server, self.plan
        n = len(plan.prompt_lens)
        late = []
        t0 = clock()
        due = [t0 + d for d in plan.due_s]
        i = 0
        while i < n or server.has_work:
            now = clock()
            while i < n and due[i] <= now:
                p = traffic.prompt_tokens(self.seed, i, plan.prompt_lens[i],
                                          self.vocab)
                self.served.append(Served(server.submit(
                    _request(i, p, plan.new_tokens[i])), p, due[i]))
                late.append(now - due[i])
                i += 1
            if server.has_work:
                server.step()
            elif i < n:
                time.sleep(min(due[i] - now, 0.002))
            if now - t0 > seconds + 120:
                raise RuntimeError("the window did not drain within 120 s")
        t1 = clock()
        log(f"generator lateness: mean {np.mean(late) * 1e3:.3f} ms, max "
            f"{np.max(late) * 1e3:.3f} ms over {n} requests")
        return t0, t1


def _percentile(values: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


def end_to_end_values(run: Run) -> Dict[str, float]:
    vals = {"setup_s": run.setup_s}
    if run.cell.mix["loop"] == "closed":
        vals["decode_tok_s"] = run.window_tokens / run.window_s
        vals["itl_p95_ms"] = _percentile(run.gaps, 95) * 1e3
    else:
        ttft = []
        for s in run.served:
            h = s.handle
            ok = h.finish_reason in GOOD_FINISH and h.first_token_at
            ttft.append((h.first_token_at if ok else run.t1) - s.due)
        vals["ttft_p95_ms"] = _percentile(ttft, 95) * 1e3
    return vals


# -- the comparison with the reference --------------------------------------

def _sample(run_served: List[Served], limits: Dict[str, Any],
            seed: int) -> List[Served]:
    """The requests to compare: every one that served a token, or a seeded
    sample of `sample_requests` with the longest prompt in it."""
    from bench import traffic
    done = [s for s in run_served if s.handle.tokens]
    k = int(limits.get("sample_requests", len(done)))
    if len(done) <= k:
        return done
    longest = max(range(len(done)), key=lambda i: len(done[i].prompt))
    rest = [i for i in range(len(done)) if i != longest]
    pick = traffic.rng_for(seed, 3).choice(rest, k - 1, replace=False)
    return [done[longest]] + [done[i] for i in sorted(pick)]


class LogitTap:
    """Keeps the logits row the server hands its sampler for every token it
    serves, per request: the timed path's own output, read from the benchmark
    by wrapping `_sample_row` on the server object."""

    def __init__(self, server):
        self.rows: Dict[int, List[np.ndarray]] = {}
        sample = server._sample_row

        def tapped(handle, row):
            self.rows.setdefault(handle.uid, []).append(
                np.array(row, dtype=np.float32))
            return sample(handle, row)
        server._sample_row = tapped


def _blocks(seqs, block_tokens: int):
    """Group (tokens, n_served, prompt_len) by length padded to 256, into
    blocks of a fixed row count per length, so each length compiles once."""
    by_len: Dict[int, List[int]] = {}
    for i, (toks, m, _) in enumerate(seqs):
        by_len.setdefault(-(-len(toks) // 256) * 256, []).append(i)
    blocks, members = [], []
    for T, idx in sorted(by_len.items()):
        rows = max(1, block_tokens // T)
        n_pos = max(seqs[i][1] for i in idx)
        n_pos = n_pos if n_pos == 1 else -(-n_pos // 128) * 128
        for b in range(0, len(idx), rows):
            block = idx[b:b + rows]
            toks = np.zeros((rows, T), np.int32)
            pos = np.zeros((rows, n_pos), np.int32)
            for r, i in enumerate(block):
                s, m, T0 = seqs[i]
                toks[r, :len(s)] = s
                pos[r] = np.minimum(np.arange(n_pos) + T0 - 1, T0 + m - 2)
            blocks.append((toks, pos))
            members.append(block)
    return blocks, members


# The reference in the configuration's stated arithmetic, which the compared
# readings are taken against (suffix `_vs_default`); the one at full float32
# (no suffix) is read as a diagnostic under `control` only.
STATED = "_vs_default"
REFERENCES = {STATED: "float32_default", "": "float32"}


def _read_logits(cand: np.ndarray, ref_lg: np.ndarray,
                 pick: np.ndarray) -> Tuple[float, float, float]:
    """Of `cand` rows [m, V] against reference rows [m, V], where `pick` [m]
    are the tokens chosen: the widest gap by which a chosen token's reference
    logit lies below the reference's best, the widest root-mean-square error
    of one row, and the sum of squared errors (for the root mean square over
    every row)."""
    gap = float((ref_lg.max(-1) - ref_lg[np.arange(len(pick)), pick]).max())
    sq = np.square(cand.astype(np.float64) - ref_lg)
    return gap, float(np.sqrt(sq.mean(-1)).max()), float(sq.sum())


def _second_best_gap(ref_lg: np.ndarray) -> float:
    """The widest gap a sampler that served the second-best token everywhere
    would read: the largest margin between a row's best and second logit."""
    top2 = np.partition(ref_lg, -2, axis=-1)[:, -2:]
    return float((top2[:, 1] - top2[:, 0]).max())


def compare(cell: Cell, dims, params, sample: List[Served],
            logits: Dict[int, List[np.ndarray]],
            control: bool) -> Dict[str, float]:
    """Run the reference once over each prompt with its served tokens, in the
    configuration's stated arithmetic (float32, one bfloat16 pass per
    product), and read at every served token (`_read_logits`): the gap of
    the served token (`logit_gap_vs_default`) and the error of the logits row
    the program served (`logit_err_max_vs_default`, `logit_err_rms_vs_default`).
    With `control`, also: the same readings for the reference one precision
    step lower, bfloat16, in the program's place (`control_`; its gap is that
    of the token it puts first); the gap a second-best-token sampler would
    read (`second_best_logit_gap_vs_default`); and the program's readings
    against the reference at full float32 (no suffix)."""
    ref = reference_module(cell)
    seqs = [(np.concatenate([s.prompt, np.asarray(s.handle.tokens[:-1],
                                                  np.int32)]),
             len(s.handle.tokens), len(s.prompt)) for s in sample]
    blocks, members = _blocks(
        seqs, int(cell.limits.get("reference_block_tokens", 4096)))
    outs = {k: ref.forward_logits(params, blocks, dims, prec)
            for k, prec in REFERENCES.items() if control or k == STATED}
    ctrl = ref.forward_logits(params, blocks, dims, "bfloat16") \
        if control else None
    acc: Dict[str, float] = collections.defaultdict(float)
    n_served, shares = 0, []
    for b, block in enumerate(members):
        share = outs[STATED][b][1]          # None: the model states none
        share = None if share is None else np.asarray(share)
        lgs = {k: np.asarray(o[b][0]) for k, o in outs.items()}
        cl = np.asarray(ctrl[b][0]) if control else None
        for r, i in enumerate(block):
            s = sample[i]
            m, T0 = seqs[i][1], seqs[i][2]
            cands = {"logit": (np.stack(logits[s.handle.uid][-m:]),
                               np.asarray(s.handle.tokens))}
            if control:
                cands["control_logit"] = (cl[r, :m], cl[r, :m].argmax(-1))
                key = "second_best_logit_gap" + STATED
                acc[key] = max(acc[key], _second_best_gap(lgs[STATED][r, :m]))
            for k, lg in lgs.items():
                for w, (cand, pick) in cands.items():
                    gap, err_max, sq = _read_logits(cand, lg[r, :m], pick)
                    acc[f"{w}_gap{k}"] = max(acc[f"{w}_gap{k}"], gap)
                    acc[f"{w}_err_max{k}"] = max(acc[f"{w}_err_max{k}"],
                                                 err_max)
                    acc[f"{w}_err_rms{k}"] += sq
            n_served += m
            if share is not None:
                shares.append(share[:, r, :T0 - 1 + m].mean())
    for key in acc:
        if "_err_rms" in key:
            acc[key] = float(np.sqrt(acc[key] / max(n_served * dims.vocab, 1)))
    acc["served_tokens"] = n_served
    if _stated_activations(cell) is not None:
        acc["token_share"] = float(np.mean(shares)) if shares else 0.0
    return dict(acc)


def _stated_activations(cell: Cell) -> Optional[Dict[str, Any]]:
    """The activation share the configuration states, if it states one."""
    return cell.config.get("assumed", {}).get("activations")


def verdict(cell: Cell, got: Dict[str, float], failed: int,
            who: str = "") -> Dict[str, Dict[str, float]]:
    """Each number compared, beside its limit: the cell's logit checks read
    for `who` ("" the program, "control_" the control in its place), where
    the configuration states an activation share, the reference's share on
    the served sequences within its stated tolerance, and no failed
    request."""
    checks = {name: {"value": got[who + name], "limit": float(limit)}
              for name, limit in cell.limits["checks"].items()}
    act = _stated_activations(cell)
    if act is not None:
        checks["activation_share_off"] = {
            "value": abs(got["token_share"] / act["share"] - 1.0),
            "limit": float(act["tolerance"])}
    checks["failed_requests"] = {"value": failed, "limit": 0}
    return checks


def passes(checks: Dict[str, Dict[str, float]], served_tokens: int) -> bool:
    return served_tokens > 0 and all(c["value"] <= c["limit"]
                                     for c in checks.values())


# -- one run ---------------------------------------------------------------------

def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, control: bool = False) -> Dict[str, Any]:
    import jax
    from repro.models import build_model
    from bench import roofline, spans as spans_mod, traffic
    from bench import trace as trace_mod

    counter = CompileCounter()
    devices = jax.devices()
    dev = devices[0]
    peak = roofline.peak_for(dev.device_kind) if dev.platform == "tpu" \
        else None
    ref = reference_module(cell)
    dims = ref.Dims.from_config(cell.config)
    cfg = program_config(cell.config)
    vocab = cfg.vocab_size
    t = time.monotonic()
    params = jax.block_until_ready(ref.make_params(dims, cell.config, seed))
    model = build_model(cfg)
    check_layout(model, params)
    log(f"weights: {time.monotonic() - t:.3f} s")
    server, runtime = _build_server(cell, model, params, seed)
    tap = LogitTap(server)
    _warm_up(cell, server, runtime, seed, vocab)
    plan = traffic.plan(cell.mix, seed, seconds)
    clock = server._clock

    tracer = None
    tdir = None
    if trace:
        tracer = spans_mod.Spans(server, runtime).install()
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
    loop = (ClosedLoop if cell.mix["loop"] == "closed" else OpenLoop)(
        cell, server, plan, seed, vocab)
    loop.prepare()
    stats = dev.memory_stats() or {}
    log(f"memory after warm-up: in use {stats.get('bytes_in_use')}, peak "
        f"{stats.get('peak_bytes_in_use')}, limit {stats.get('bytes_limit')}")
    n_compiled = counter.n
    hist0 = _program_counters(server, runtime)
    if trace:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0      # the bench.* spans suffice
        jax.profiler.start_trace(tdir, profiler_options=options)
    try:
        if tracer is not None:
            tracer.log.recording = True
        with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
            t0, t1 = loop.run(seconds, clock)
    finally:
        if trace:
            jax.profiler.stop_trace()
    compiles = counter.n - n_compiled
    setup_s = t0 - t_start
    hist1 = _program_counters(server, runtime)
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devices[:cell.chips])
    union_share = _union_share(runtime, hist0, hist1, cfg.d_ff)

    in_window = loop.served
    tokens, gaps = 0, []
    for s in in_window:
        tt = s.handle.token_times
        for j, tj in enumerate(tt):
            if t0 <= tj <= t1:
                tokens += 1
                if j:
                    gaps.append(tj - tt[j - 1])
    run = Run(cell=cell, dims=dims, peak=peak, t0=t0, t1=t1, setup_s=setup_s,
              served=in_window, window_tokens=tokens, gaps=gaps,
              n_mats=dims.n_mats,
              compiles_in_window=compiles, memory_peak_bytes=int(mem),
              work=tracer.log if tracer else None)
    if runtime is not None:
        run.extents = (hist1["ops"] - hist0["ops"],
                       hist1["steps"] - hist0["steps"])
        run.exposed = (hist1["exposed"] - hist0["exposed"],
                       hist1["sched_steps"] - hist0["sched_steps"])
    if tracer is not None:
        tracer.uninstall()
    server.close()
    attempted = len(in_window)
    failed = sum(s.handle.done and s.handle.finish_reason not in GOOD_FINISH
                 for s in in_window)
    log(f"window {run.window_s:.3f} s: {attempted} requests, {failed} failed, "
        f"{tokens} tokens, {compiles} programs compiled or loaded in it")
    if union_share is not None:
        log(f"neurons served per layer and decode step (lookahead speculation "
            f"and top-ups): {union_share:.4f} of d_ff")
    if tracer is not None and tracer.log.unions:
        log(f"true activated union per layer and decode step: "
            f"{np.mean([u for _, u in tracer.log.unions]) / cfg.d_ff:.4f} "
            f"of d_ff")

    if trace:
        events = trace_mod.load_events(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        run.trace = trace_mod.reduce_events(events)
    metrics = _metrics(run, trace)

    # free the program's state before the reference runs beside the weights
    sample = _sample(in_window, cell.limits, seed)
    del server, runtime, model, tracer, loop
    gc.collect()
    t = time.monotonic()
    got = compare(cell, dims, params, sample, tap.rows, control)
    log(f"reference over {len(sample)} requests, {got['served_tokens']} "
        f"served tokens: {time.monotonic() - t:.3f} s")
    act = _stated_activations(cell)
    if act is not None:
        log(f"activation share per token {got['token_share']:.5f} (config "
            f"{act['share']} +- {act['tolerance'] * 100:.0f}%)")
    log("readings: " + ", ".join(f"{k} {v!r}" for k, v in got.items()))

    checks = verdict(cell, got, failed)
    correct = passes(checks, got["served_tokens"])
    if control:
        # the control in the program's place decides `correct`; the
        # program's own verdict on the same run is kept as a reading
        got["program_correct"] = correct
        for name, c in checks.items():
            log(f"program check {name}: {c['value']!r} (limit {c['limit']!r})")
        checks = verdict(cell, got, failed, who="control_")
        correct = passes(checks, got["served_tokens"])
    result = {
        "correct": bool(correct), "attempted": attempted, "failed": failed,
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": int(mem)},
    }
    if trace:
        result["device"]["busy_s"] = run.trace.busy_s
        result["device"]["window_s"] = run.trace.window_s
        result["breakdown"] = {"device_ops": run.trace.top_ops(),
                               "idle_gaps": run.trace.top_idle()}
    result["readings"] = got
    result["checks"] = checks
    return result


def _program_counters(server, runtime) -> Dict[str, float]:
    if runtime is None:
        return {}
    hist = [e.history for e in runtime.engines]
    sched = server.scheduler.history
    return {"ops": sum(t.io.n_ops for h in hist for t in h),
            "steps": len(hist[0]),
            "union": sum(t.n_activated for h in hist for t in h),
            "layer_steps": sum(len(h) for h in hist),
            "exposed": sum(t.measured_exposed_seconds for t in sched),
            "sched_steps": len(sched)}


def _union_share(runtime, h0, h1, d_ff) -> Optional[float]:
    if runtime is None or h1["layer_steps"] == h0["layer_steps"]:
        return None
    return ((h1["union"] - h0["union"])
            / (h1["layer_steps"] - h0["layer_steps"]) / d_ff)


def _metrics(run: Run, trace: bool) -> Dict[str, Dict[str, Any]]:
    out: Dict[str, Dict[str, Any]] = {}
    if not trace:
        vals = end_to_end_values(run)
        for m in run.cell.end_to_end:
            out[m["name"]] = {"value": vals[m["name"]], "unit": m["unit"]}
        return out
    for m in run.cell.per_layer:
        mod = load_module(run.cell.bench / "metrics" / f"{m['name']}.py")
        v = mod.read(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def print_result(result: Dict[str, Any]) -> None:
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
