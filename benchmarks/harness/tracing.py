"""The traced slice of a window and the benchmark's own host spans.

Spans are ``jax.profiler.TraceAnnotation``s named ``bench.<what>``: they
land in the profiler's own trace, on the same clock as the device's
operations, and cost next to nothing while no trace is recording.  They
are flat (never nested), so that an idle gap has one owner.
"""
from __future__ import annotations

import os
import shutil

import jax

from . import trace_reduce

SLICE_SECONDS = 3.0


def span(what: str):
    return jax.profiler.TraceAnnotation(trace_reduce.HOST_SPAN_PREFIX + what)


class SliceTracer:
    """Traces the last ``SLICE_SECONDS`` of a window.  The kind calls
    ``tick(now)`` between steps and ``finish()`` after the window has
    closed; with ``enabled`` false both do nothing."""

    def __init__(self, enabled: bool, trace_dir: str):
        self.enabled = enabled
        self.trace_dir = trace_dir
        self.start_at = None
        self.running = False
        self.reduced = None
        self.path = None        # the .xplane.pb this run wrote

    def arm(self, window_start: float, seconds: float):
        self.start_at = window_start + max(
            0.0, seconds - min(SLICE_SECONDS, seconds / 2))

    def tick(self, now: float):
        if self.enabled and not self.running and self.start_at is not None \
                and now >= self.start_at:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            os.makedirs(self.trace_dir, exist_ok=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0   # no event per Python call
            options.host_tracer_level = 2     # TraceAnnotations
            jax.profiler.start_trace(self.trace_dir,
                                     profiler_options=options)
            self.running = True

    def finish(self):
        if not self.running:
            return
        jax.profiler.stop_trace()
        self.running = False
        self.path = trace_reduce.newest_xplane(self.trace_dir)
        self.reduced = trace_reduce.reduce_trace(
            trace_reduce.read_xplane(self.path))
