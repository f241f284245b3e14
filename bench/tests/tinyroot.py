"""A checkout-like directory holding the benchmark with tiny cells, for
driving the harness on the CPU: two of a tiny OPT configuration, and two of a
configuration of another shape (grouped-query attention, RMSNorm, a gated
SiLU FFN) that brings its own reference module, added as new files only."""
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
GQA_CELLS = {"tiny-gqa.decode": "tiny_gqa_decode",
             "tiny-gqa.prefill": "tiny_prefill"}


def _add_cell(spec, root: Path, cell: str, config: str, mix: str,
              limits) -> None:
    shutil.copy(DATA / f"{mix}.json", root / "bench" / "traffic" / f"{mix}.json")
    (root / "bench" / "limits" / f"{cell}.json").write_text(json.dumps(limits))
    spec["workloads"].append({"name": cell, "config": config, "traffic": mix,
                              "chips": 1, "why": "test"})
    kind = "decode" if "decode" in cell else "prefill"
    for m in spec["end_to_end"] + spec["per_layer"]:
        if any(kind in w for w in m.get("workloads", [])):
            m["workloads"].append(cell)


def make(tmp: Path) -> Path:
    """Copy BENCHMARK.json and bench/ into `tmp`, and add the two tiny
    configurations (the second with its reference module), their mixes,
    limits and cells."""
    root = tmp / "checkout"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    shutil.copy(DATA / "tiny.json", root / "bench" / "configs" / "tiny.json")
    shutil.copy(DATA / "tiny_gqa.json",
                root / "bench" / "configs" / "tiny-gqa.json")
    shutil.copy(DATA / "gated_gqa.py",
                root / "bench" / "reference" / "gated_gqa.py")
    for name in ("tiny", "tiny-gqa"):
        spec["configs"].append({"name": name, "source": "test",
                                "file": f"bench/configs/{name}.json",
                                "reduced": [], "why": "test"})
    for cell, mix in TINY_CELLS.items():
        committed = json.loads(
            (REPO / "bench" / "limits" / f"{COMMITTED[cell]}.json").read_text())
        _add_cell(spec, root, cell, "tiny", mix,
                  {"checks": committed["checks"], "sample_requests": 4,
                   "reference_block_tokens": 256})
    gqa_limits = json.loads((DATA / "tiny_gqa_limits.json").read_text())
    for cell, mix in GQA_CELLS.items():
        _add_cell(spec, root, cell, "tiny-gqa", mix, gqa_limits)
    (root / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return root
