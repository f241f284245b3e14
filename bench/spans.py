"""Host spans and work records around each layer's entry point, installed
from the benchmark onto the program's objects (never by editing them).

Each wrapper opens a `jax.profiler.TraceAnnotation` named `bench.<layer>`, so
the spans land in the profiler's trace on its own clock, and the device's
idle gaps can be named by what the host was doing. The decode-step and mask
wrappers also record the work of each call (slot positions, activated
unions), from which the roofline readers count operations and bytes.
Installed only for a traced run; `uninstall` puts everything back.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, List, Tuple

import jax
import numpy as np


@dataclasses.dataclass
class WorkLog:
    # one entry per decode step: positions of the active slots
    steps: List[np.ndarray] = dataclasses.field(default_factory=list)
    # one entry per dense-FFN call: (active rows, activated-union size)
    unions: List[Tuple[int, int]] = dataclasses.field(default_factory=list)
    recording: bool = False


def _annotated(name: str, fn: Callable) -> Callable:
    @functools.wraps(fn)
    def wrapper(*a, **kw):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **kw)
    return wrapper


class Spans:
    """Wraps the server, the offload runtime and the kernel entry points."""

    def __init__(self, server, runtime=None):
        self.server, self.runtime = server, runtime
        self.log = WorkLog()
        self._undo: List[Callable[[], None]] = []

    def _patch(self, obj, attr: str, new: Callable) -> None:
        had = attr in vars(obj)
        old = vars(obj).get(attr)
        setattr(obj, attr, new)
        self._undo.append((lambda: setattr(obj, attr, old)) if had
                          else (lambda: delattr(obj, attr)))

    def install(self) -> "Spans":
        from repro.kernels import ops
        s, log = self.server, self.log
        self._patch(s, "step", _annotated("bench.step", s.step))
        self._patch(s, "_admit", _annotated("bench.admit", s._admit))

        decode = s._decode_iteration

        def decode_iteration():
            if log.recording:
                active = s._active_mask()
                log.steps.append(np.asarray(s._slot_pos)[active].copy())
            with jax.profiler.TraceAnnotation("bench.decode_step"):
                return decode()
        self._patch(s, "_decode_iteration", decode_iteration)

        if s.mode == "offload":
            masks_fn = s._true_masks

            def true_masks(dense_idx, h2, active):
                with jax.profiler.TraceAnnotation("bench.masks"):
                    m = masks_fn(dense_idx, h2, active)
                if log.recording:
                    log.unions.append((int(active.sum()),
                                       int(m.any(axis=0).sum())))
                return m
            self._patch(s, "_true_masks", true_masks)
            rt = self.runtime
            for attr in ("begin_layer", "predict_lookahead",
                         "complete_layer", "segment_kernel_inputs"):
                self._patch(rt, attr, _annotated(f"bench.{attr}",
                                                 getattr(rt, attr)))
        for attr in ("sparse_ffn_segments_fused", "paged_decode_attention"):
            self._patch(ops, attr, _annotated(f"bench.{attr}",
                                              getattr(ops, attr)))
        return self

    def uninstall(self) -> None:
        while self._undo:
            self._undo.pop()()
