"""Offline packing driver: run the paper's whole offline stage and write the
deployable NeuronPack artifact.

  PYTHONPATH=src python -m repro.launch.pack --arch qwen2-7b --reduced \
      --out model.npack [--calib-tokens 512] [--quantize int8] \
      [--no-placement] [--placement-mode auto|exact|topk] \
      [--d-model N] [--d-ff N] [--n-layers N]

The pack records the model's flash bundles in physical (linked-placement)
order plus the per-layer placement tables; serve it with
``repro.launch.serve --mode offload --pack model.npack`` built from the SAME
--arch/--seed/geometry flags (weights are deterministic from the seed, and
load-time validation rejects geometry mismatches).
"""
import argparse
import time

import jax

from repro.configs import ALL_CONFIGS, get_config
from repro.models import build_model
from repro.store.packer import build_pack
from repro.utils import (add_verbosity_flag, configure_logging,
                         enable_compile_cache, get_logger)

logger = get_logger("launch.pack")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b", choices=sorted(ALL_CONFIGS))
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="tiny same-family config (default); --no-reduced "
                         "packs the published widths and depth")
    ap.add_argument("--out", required=True, help="output NeuronPack path")
    ap.add_argument("--calib-tokens", type=int, default=512,
                    help="random calibration tokens to trace (streamed to "
                         "disk shards, so this can exceed RAM)")
    ap.add_argument("--calib-batch", type=int, default=8)
    ap.add_argument("--calib-seqlen", type=int, default=64)
    ap.add_argument("--quantize", choices=("none", "int8"), default="none",
                    help="int8 = per-neuron symmetric quantized bundles with "
                         "float32 scales")
    ap.add_argument("--no-placement", action="store_true",
                    help="identity layout (LLMFlash-style baseline pack)")
    ap.add_argument("--placement-mode", choices=("auto", "exact", "topk"),
                    default="auto")
    ap.add_argument("--pack-version", type=int, choices=(1, 2), default=2,
                    help="NeuronPack format version: 2 (default) adds the "
                         "header CRC + per-bundle CRC32 tables that "
                         "--verify-checksums serving checks; 1 writes the "
                         "legacy checksum-free layout")
    ap.add_argument("--shard-dir", default=None,
                    help="keep trace shards here (default: temp dir, deleted)")
    ap.add_argument("--d-model", type=int, default=None)
    ap.add_argument("--d-ff", type=int, default=None)
    ap.add_argument("--n-layers", type=int, default=None)
    ap.add_argument("--vocab", dest="vocab_size", type=int, default=None,
                    help="override the vocabulary size (default: the "
                         "config's own, capped at 512 by --reduced)")
    ap.add_argument("--seed", type=int, default=0)
    add_verbosity_flag(ap)
    args = ap.parse_args(argv)
    configure_logging(args.verbose)

    overrides = dict(activation="relu")
    for key in ("d_model", "d_ff", "n_layers", "vocab_size"):
        val = getattr(args, key)
        if val is not None:
            overrides[key] = val
    cfg = get_config(args.arch, reduced=args.reduced, **overrides)
    if cfg.family != "dense" or cfg.is_encdec:
        raise SystemExit("packing is implemented for dense decoder-only archs")
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(args.seed))

    t0 = time.perf_counter()
    report = build_pack(
        model, params, args.out,
        calib_tokens=args.calib_tokens, calib_batch=args.calib_batch,
        calib_seqlen=args.calib_seqlen, seed=args.seed,
        use_placement=not args.no_placement,
        placement_mode=args.placement_mode, quantize=args.quantize,
        shard_dir=args.shard_dir, pack_version=args.pack_version,
        meta=dict(arch=args.arch, seed=args.seed, vocab_size=cfg.vocab_size))
    logger.info(
        "packed %d layers x %d neurons x %d floats -> %s (%.1f MB, %s, "
        "%s layout) in %.1fs: traced %d tokens, placement search %.2fs",
        report.n_layers, report.n_neurons, report.bundle_width, report.path,
        report.file_bytes / 1e6,
        "int8" if report.quantized else "float32", report.placement_mode,
        time.perf_counter() - t0, report.tokens_traced, report.search_seconds)
    logger.info("serve it: PYTHONPATH=src python -m repro.launch.serve "
                "--arch %s --mode offload --pack %s --seed %d",
                args.arch, report.path, args.seed)


if __name__ == "__main__":
    enable_compile_cache()
    main()
