"""Device: peak HBM in use on the fullest chip over the run, as the runtime
reports it (`peak_bytes_in_use`) (GB). Memory bounds the batch."""


def read(run):
    return run.memory_peak_bytes / 1e9 if run.memory_peak_bytes else None
