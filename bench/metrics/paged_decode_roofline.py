"""Kernels: the paged-decode attention kernel's share of its roofline (%):
the least time the chip needs to read the K/V of every active slot's filled
positions (plus queries in and outputs out), for every layer of every decode
step in the traced window, over the kernel's device time in the trace."""
from bench import roofline

KERNEL = ("paged_decode",)


def read(run):
    if run.trace is None or not run.work or not run.work.steps:
        return None
    seconds = run.trace.seconds_matching(KERNEL)
    if seconds <= 0:
        return None
    d = run.dims
    per_step = [roofline.paged_decode_work(pos, d.n_heads, d.n_kv_heads,
                                           d.head_dim)
                for pos in run.work.steps]
    work = roofline.total(per_step)
    work = roofline.Work(work.flops * d.n_layers, work.bytes * d.n_layers)
    return 100.0 * work.min_seconds(run.peak) / seconds
