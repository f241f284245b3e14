"""Run one benchmark cell on the chip this process holds.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. Makes the weights and the traffic from the seed,
warms up, measures for `--seconds`, checks what was served against the plain
reference, and prints one JSON line last on standard output: `correct`,
`attempted`, `failed`, `metrics` (the cell's end-to-end metrics, or with
`--trace 1` its per-layer ones), `device`, with `--trace 1` a `breakdown`,
and last the `checks` compared, each with its limit. Exits non-zero, printing
no result, without a TPU or with fewer chips than the cell needs.

`--control 1` puts the control in the program's place: the reference
computed one precision step lower (bfloat16) on the same served tokens, whose
readings then decide `correct` (it has to come out false). The program's own
verdict on the run is kept as the reading `program_correct`, beside the
diagnostic readings against full float32 and the gap a second-best-token
sampler would read. The benchmark's own runs leave it off.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    cell = harness.load_cell(args.workload)
    harness.enable_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        harness.log(f"needs {cell.chips} TPU chip(s); JAX found "
                    f"{len(devices)} {devices[0].platform} device(s)")
        return 2
    result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                              T_START, control=bool(args.control))
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
