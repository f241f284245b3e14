"""A checkout-like directory holding the benchmark with two tiny cells, for
driving the harness on the CPU."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
TINY_CELLS = {"tiny.decode": "tiny_decode", "tiny.prefill": "tiny_prefill"}
# each tiny cell is held to the checks and limits of the committed cell
# whose path it drives
COMMITTED = {"tiny.decode": "opt-1.3b.decode",
             "tiny.prefill": "opt-1.3b.prefill"}


def make(tmp: Path) -> Path:
    """Copy BENCHMARK.json and bench/ into `tmp`, and add the tiny config,
    its two mixes, their limits (the committed cells' checks) and their
    cells."""
    root = tmp / "checkout"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    shutil.copy(DATA / "tiny.json", root / "bench" / "configs" / "tiny.json")
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "bench/configs/tiny.json", "reduced": [],
                            "why": "test"})
    for cell, mix in TINY_CELLS.items():
        shutil.copy(DATA / f"{mix}.json",
                    root / "bench" / "traffic" / f"{mix}.json")
        committed = json.loads(
            (REPO / "bench" / "limits" / f"{COMMITTED[cell]}.json").read_text())
        (root / "bench" / "limits" / f"{cell}.json").write_text(json.dumps(
            {"checks": committed["checks"], "sample_requests": 4,
             "reference_block_tokens": 256}))
        spec["workloads"].append({"name": cell, "config": "tiny",
                                  "traffic": mix, "chips": 1, "why": "test"})
        for m in spec["end_to_end"] + spec["per_layer"]:
            kind = "decode" if "decode" in cell else "prefill"
            if any(kind in w for w in m.get("workloads", [])):
                m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return root
