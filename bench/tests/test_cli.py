"""`bench/run.py` refuses to run without the chips its cell asks for, and
without the program beside it."""
import json
import os
import shutil
import subprocess
import sys

from bench.tests import tinyroot

ARGS = ["--workload", "opt-1.3b.decode", "--seed", "3", "--seconds", "1",
        "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def _printed_result(out: str) -> bool:
    for line in out.splitlines():
        try:
            if "correct" in json.loads(line):
                return True
        except (ValueError, TypeError):
            pass
    return False


def test_exits_nonzero_without_a_tpu():
    p = _run(tinyroot.REPO)
    assert p.returncode != 0
    assert not _printed_result(p.stdout)
    assert "TPU" in p.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(tinyroot.REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(tinyroot.REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert not _printed_result(p.stdout)
