"""Kernels: the fused sparse-FFN kernel's share of its roofline (%): the least
time the chip needs for the activated unions it served in the traced window
(weights of the union's neurons, activations in and out; the larger of the
compute and memory bounds), over the kernel's device time in the trace."""
from bench import roofline

KERNEL = ("sparse_ffn_segments_fused",)


def read(run):
    if run.trace is None or not run.work or not run.work.unions:
        return None
    seconds = run.trace.seconds_matching(KERNEL)
    if seconds <= 0:
        return None
    d = run.dims
    work = roofline.total(
        roofline.sparse_ffn_work(rows, union, run.n_mats, d.d_model)
        for rows, union in run.work.unions)
    return 100.0 * work.min_seconds(run.peak) / seconds
