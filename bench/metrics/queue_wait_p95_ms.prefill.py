"""Scheduler: 95th percentile of the wait from when a request was due to
when the server admitted it, over every request due in the window (ms)."""
import numpy as np


def read(run):
    waits = [s.handle.admitted_at - s.due for s in run.served
             if s.due is not None and s.handle.admitted_at is not None]
    return float(np.percentile(waits, 95)) * 1e3 if waits else None
