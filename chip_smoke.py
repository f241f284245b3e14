"""Smoke run of offload serving on one TPU at the full width of OPT-1.3B.

  python chip_smoke.py          # with JAX_PLATFORMS unset, on a TPU host

Serves 8 requests through `repro.launch.serve.serve` (the `python -m
repro.launch.serve` code path) at OPT-1.3B's published widths and depth with
seeded random weights: calibration forward, co-activation placement search,
lookahead training, the prefetch worker, the fused segment-FFN Pallas kernel
and the paged-decode Pallas kernel. It then checks that

  * every request finished with "length" and its full token count,
  * the offload runtime resolved its FFN path to the fused `segments` kernel,
  * no fault-tolerance fallback engaged (retries, corrupt extents, degraded
    steps, prefetch worker restarts are all 0),
  * the Pallas kernel agrees with its XLA twin on one layer's real inputs.

Progress lines go to stdout; the last line is one JSON object,
{"ok": true, "device": {"platform", "kind", "count"}}. Without a TPU, or when
any check fails, it exits non-zero and prints no such line. Everything runs in
this one process, which holds the chip.
"""
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SERVE_ARGV = ["--arch", "opt-1.3b", "--no-reduced", "--mode", "offload",
              "--prefetch", "--slots", "4", "--requests", "8",
              "--prompt-len", "64", "--new-tokens", "16",
              "--page-size", "16", "--num-pages", "64", "--seed", "0"]
N_REQUESTS, NEW_TOKENS = 8, 16
KERNEL_LAYER = 0
# Max |kernel - twin| over max |twin|. The twin runs at "highest" matmul
# precision; the kernel's f32 dots may take a single bf16 MXU pass, which
# rounds each operand to 8 significant bits (2^-9 relative). Over the two
# chained contractions (d_model, then the served neurons) the random-sign
# rounding errors stay a few 2^-9 of the output's scale, so 1e-2 leaves
# margin; a wrong segment, scale row or mask is an O(1) error.
KERNEL_TOL = 1e-2


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def check_serving(out: dict) -> None:
    """Raise unless every request ran to length on the fused-kernel path
    with no fault-tolerance fallback engaged."""
    results = out["results"]
    finished = [(r.finish_reason, len(r.tokens)) for r in results]
    if len(results) != N_REQUESTS or any(
            f != ("length", NEW_TOKENS) for f in finished):
        raise RuntimeError(f"requests did not all finish 'length' with "
                           f"{NEW_TOKENS} tokens: {finished}")
    io = out["io_summary"]
    if io["ffn_kernel"] != "segments":
        raise RuntimeError(f"ffn_kernel resolved to {io['ffn_kernel']!r} "
                           f"({io['ffn_kernel_decision']}), not 'segments'")
    counters = {k: io[k] for k in ("degraded_steps", "worker_restarts",
                                   "retries", "corrupt_extents")}
    if any(counters.values()):
        raise RuntimeError(f"fault-tolerance fallback engaged: {counters}")


def kernel_error(out: dict, seed: int = 0) -> float:
    """Max |Pallas kernel - XLA twin| / max |twin| for the fused segment FFN
    of layer KERNEL_LAYER, on that layer's captured FFN inputs (last position
    of 8 seeded prompts) and their activated union."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops

    model, params, offload = out["model"], out["params"], out["offload"]
    cfg = model.cfg
    tokens = np.random.default_rng(seed).integers(0, cfg.vocab_size, (8, 64))
    cap = model.forward(params, {"tokens": jnp.asarray(tokens, jnp.int32)},
                        capture_activations=True)
    h = cap["ffn_inputs"][KERNEL_LAYER, :, -1]
    ids = np.flatnonzero(
        np.asarray(cap["ffn_pre_act"][KERNEL_LAYER, :, -1] > 0).any(axis=0))
    args = offload.segment_kernel_inputs(KERNEL_LAYER, ids)
    kw = dict(seg_size=offload.engine_cfg.kernel_seg_size,
              activation=cfg.activation)
    got = ops.sparse_ffn_segments_fused(h, *args, interpret=False, **kw)
    with jax.default_matmul_precision("highest"):
        want = ops._sparse_ffn_segments_fused_xla(h, *args, **kw)
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape or not np.isfinite(got).all():
        raise RuntimeError(f"kernel output {got.shape} is not a finite "
                           f"match for the twin's {want.shape}")
    say(f"kernel check: layer {KERNEL_LAYER}, {ids.size} neurons in "
        f"{int((np.asarray(args[2]) >= 0).sum())} segments, batch {h.shape[0]}")
    return float(np.abs(got - want).max() / np.abs(want).max())


def main() -> int:
    from repro.utils import enable_compile_cache
    cache = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    say(f"device {dev.device_kind} x{len(devices)}; compile cache {cache}")

    from repro.launch.serve import serve
    t0 = time.perf_counter()
    out = serve(SERVE_ARGV)
    t = out["timings"]
    n_tok = sum(len(r.tokens) for r in out["results"])
    search = sum(e.placement.search_seconds for e in out["offload"].engines)
    say(f"setup seconds: init {t['init']:.3f}, calibration + placement "
        f"{t['calibration']:.3f} (placement search {search:.3f} of it), "
        f"first step (compile) {t['first_step']:.3f}")
    say(f"served {len(out['results'])} requests, {n_tok} tokens in "
        f"{t['serve']:.3f} s wall ({time.perf_counter() - t0:.3f} s with setup)")
    stats = dev.memory_stats() or {}
    say(f"HBM bytes_in_use {stats.get('bytes_in_use')} "
        f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    say(f"ffn_kernel {out['io_summary']['ffn_kernel']}; finish reasons "
        f"{sorted({r.finish_reason for r in out['results']})}")
    check_serving(out)

    err = kernel_error(out)
    say(f"kernel vs XLA twin: max error {err:.3e} of max |twin| "
        f"(tolerance {KERNEL_TOL:.0e})")
    if not err <= KERNEL_TOL:
        raise RuntimeError(f"fused kernel differs from its twin by {err:.3e}")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
