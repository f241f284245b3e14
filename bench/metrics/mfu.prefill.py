"""Device: the whole admission prefill's share of the chip's bf16 peak (%):
the dense-equivalent FLOPs of every prompt prefilled in the window (every
weight once per prompt token, causal attention, the unembedding of the last
position), over the window's length and the peak."""
from bench import roofline


def read(run):
    d = run.dims
    done = [s for s in run.served if s.handle.tokens]
    if not done:
        return None
    flops = sum(roofline.prefill_request_flops(
        len(s.prompt), n_layers=d.n_layers, d_model=d.d_model, d_ff=d.d_ff,
        n_heads=d.n_heads, n_kv_heads=d.n_kv_heads, vocab=d.vocab,
        n_mats=run.n_mats) for s in done)
    return 100.0 * flops / run.window_s / run.peak.flops
