"""Model step: median admission-prefill time per request, the server's own
`prefill_seconds` (prefill through the sync on its logits) (ms)."""
import numpy as np


def read(run):
    t = [s.handle.prefill_seconds for s in run.served
         if s.handle.admitted_at is not None and s.handle.tokens]
    return float(np.median(t)) * 1e3 if t else None
