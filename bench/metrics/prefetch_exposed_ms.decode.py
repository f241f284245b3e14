"""Offload runtime: host time per decode step that the serving thread spent
waiting on the prefetch worker or topping up its misses (the scheduler's
measured exposed seconds), over the window (ms)."""


def read(run):
    if not run.exposed or not run.exposed[1]:
        return None
    seconds, steps = run.exposed
    return seconds / steps * 1e3
