"""Device: the whole decode step's share of the chip's bf16 peak (%): the
dense-equivalent FLOPs of every token decoded in the window (every weight
once, attention over the token's context, the unembedding), over the
window's length and the peak."""
from bench import roofline


def read(run):
    if not run.work or not run.work.steps:
        return None
    d = run.dims
    flops = sum(roofline.decode_token_flops(
        int(p), n_layers=d.n_layers, d_model=d.d_model, d_ff=d.d_ff,
        n_heads=d.n_heads, n_kv_heads=d.n_kv_heads, vocab=d.vocab,
        n_mats=run.n_mats)
        for step in run.work.steps for p in step)
    return 100.0 * flops / run.window_s / run.peak.flops
