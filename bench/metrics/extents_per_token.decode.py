"""Offload runtime: collapsed extents the layer engines read per decode step
(one token per active slot), summed over layers, over the window: the
paper's I/O-operation count. A count of the engines' extents, not a time."""


def read(run):
    if not run.extents or not run.extents[1]:
        return None
    ops, steps = run.extents
    return ops / steps
