"""Serving entry point: flags that reach full width, the exit status, and
where the compile cache goes."""
from pathlib import Path

import pytest

from repro.kernels import ops
from repro.launch import serve as serve_mod
from repro.utils import compile_cache_dir

TINY = ["--arch", "opt-350m", "--mode", "offload", "--requests", "2",
        "--slots", "2", "--prompt-len", "8", "--new-tokens", "3"]


def test_parser_reaches_full_width():
    ap = serve_mod._parser()
    default = ap.parse_args([])
    assert default.reduced and default.vocab is None
    full = ap.parse_args(["--arch", "opt-1.3b", "--no-reduced"])
    assert full.arch == "opt-1.3b" and not full.reduced


def test_offload_serve_runs_fused_kernel_path():
    out = serve_mod.serve(TINY)
    assert [(r.finish_reason, len(r.tokens)) for r in out["results"]] \
        == [("length", 3)] * 2
    io = out["io_summary"]
    assert io["ffn_kernel"] == "segments"
    assert not any(io[k] for k in ("degraded_steps", "worker_restarts",
                                   "retries", "corrupt_extents"))
    assert out["model"].cfg.vocab_size == 512      # reduced caps the vocab


def test_serve_exits_nonzero_when_a_request_errors(monkeypatch):
    def broken_ffn(*args, **kwargs):
        raise RuntimeError("kernel failed")

    monkeypatch.setattr(ops, "sparse_ffn_segments_fused", broken_ffn)
    with pytest.raises(SystemExit) as exc:
        serve_mod.main(TINY)
    assert exc.value.code not in (0, None)
    assert "finish_reason='error'" in str(exc.value.code)


def test_compile_cache_dir(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache_dir() == "/elsewhere/cache"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    repo = Path(serve_mod.__file__).resolve().parents[3]
    assert compile_cache_dir() == str(repo / ".jax_cache")
