"""Which form of a serving step is traced, and how its kernels lower.

The fused paged-attention decode kernel, the fused chunk kernel and the
RMSNorm->matmul fusions change WHICH program the model traces to, so
the decision is made at trace time and holds for the lifetime of a
compiled step (the zero-retrace contract).  The step builders in
models/generation.py resolve the mode ONCE per step and pin it around
the traced body with ``serving_fusion(...)``; the model code asks
``fusion_enabled()`` wherever the fused and the gather paths fork.

The path (``fusion_enabled``):
  1. never fused while a mesh is live (the kernels have no
     partitioning rule; asked at trace time, because a mesh may be
     installed after a step is built);
  2. else the mode pinned by ``serving_fusion(...)``;
  3. else fused, on every backend.

The lowering (``pallas_lowering``): the fused math runs as its Pallas
kernels on a TPU and as the numerically-identical XLA lowering
elsewhere, so tier-1 on the CPU guards by default the math the chip
runs.  ``force_pallas_interpret()`` puts the real ``pallas_call`` into a
trace on any backend, for the analysers.
"""
from __future__ import annotations

import contextlib
import threading

import jax

_tls = threading.local()


def fusion_enabled() -> bool:
    """The trace-time fork between the fused and the gather path that
    the model code asks."""
    from ..distributed.mesh import mesh_live

    if getattr(_tls, "override", None) is False:
        return False
    return not mesh_live()


def resolve_serving_fusion(fused=None) -> bool:
    """Pin a step's fusion mode: an explicit request wins, else fused.
    Called once per step build so the compiled program never flips mode
    between calls."""
    return True if fused is None else bool(fused)


@contextlib.contextmanager
def serving_fusion(enabled: bool):
    """Force the fusion mode for the duration (used around traced step
    bodies; runs at trace time, costs nothing per executed step)."""
    prev = getattr(_tls, "override", None)
    _tls.override = bool(enabled)
    try:
        yield
    finally:
        _tls.override = prev


@contextlib.contextmanager
def force_pallas_interpret(enabled: bool = True):
    """Trace-time context: kernels that would pick the XLA fallback
    off-TPU resolve ``use_pallas=True, interpret=True`` instead, so the
    traced program carries the REAL pallas_call leaves.  Off-TPU the
    fused steps normally lower to the XLA fallback, which is right for
    execution but blinds static analysis: the fusion miner's F004
    already-fused accounting and the priced-pallas CI gates need the
    kernel to appear in the jaxpr on any backend (analysis-only —
    interpret execution is slow and never the serving path)."""
    prev = getattr(_tls, "force_interpret", None)
    _tls.force_interpret = bool(enabled)
    try:
        yield
    finally:
        _tls.force_interpret = prev


def pallas_lowering(use_pallas=None, interpret=None):
    """``(use_pallas, interpret)`` for a kernel wrapper: what the caller
    passed, else the forced-interpret context, else Pallas exactly on a
    TPU backend and ``interpret`` exactly off it."""
    if use_pallas is None and getattr(_tls, "force_interpret", False):
        return True, True
    on_tpu = jax.default_backend() == "tpu"
    return (on_tpu if use_pallas is None else bool(use_pallas),
            not on_tpu if interpret is None else bool(interpret))
