"""Sweep the offered rate of an open-loop cell to find the highest rate the
server sustains (its knee), in one process on the chip.

  python bench/knee.py --workload opt-1.3b.prefill --seed 1 \
      --rates 10,20,30,40 --seconds 15

For each rate it runs the cell's mix at that rate on one server and prints a
JSON line: time to first token (median, 95th percentile), the completed rate,
and how far the first token of the last fifth of the requests lagged behind
that of the first fifth. A sustained rate completes at the offered rate and
does not lag; above the knee the queue grows all through the window. The
cell's own rate is set at about four fifths of the knee.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    import numpy as np
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)

    from bench import harness, traffic
    cell = harness.load_cell(args.workload)
    if cell.mix["loop"] != "open":
        raise SystemExit("the knee is a property of an open-loop cell")
    harness.enable_cache()
    import jax
    from repro.models import build_model
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("needs a TPU")
    ref = harness.reference_module(cell)
    dims = ref.Dims.from_config(cell.config)
    params = ref.make_params(dims, cell.config, args.seed)
    model = build_model(harness.program_config(cell.config))
    server, runtime = harness._build_server(cell, model, params, args.seed)
    harness._warm_up(cell, server, runtime, args.seed, dims.vocab)
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = dict(cell.mix, rate_per_s=rate)
        run_cell = dataclasses.replace(cell, mix=mix)
        plan = traffic.plan(mix, args.seed, args.seconds)
        loop = harness.OpenLoop(run_cell, server, plan, args.seed, dims.vocab)
        t0, t1 = loop.run(args.seconds, server._clock)
        ttft = np.array([s.handle.first_token_at - s.due for s in loop.served])
        fifth = max(1, len(ttft) // 5)
        print(json.dumps({
            "rate_per_s": rate, "requests": len(ttft),
            "completed_per_s": len(ttft) / (t1 - t0),
            "ttft_p50_ms": float(np.median(ttft)) * 1e3,
            "ttft_p95_ms": float(np.percentile(ttft, 95)) * 1e3,
            "lag_ms": float(np.median(ttft[-fifth:])
                            - np.median(ttft[:fifth])) * 1e3,
            "drain_s": (t1 - t0) - args.seconds}), flush=True)
        server.release_finished()
    server.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
