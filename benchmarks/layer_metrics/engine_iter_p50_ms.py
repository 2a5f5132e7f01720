"""Median time of one ``Engine.step()`` (admit, prefill chunks under the
budget, one decode step, host argmax and bookkeeping) on the benchmark's
clock, over the window's iterations."""
from benchmarks.harness.stats import percentile


def read(run):
    return percentile(run.get("iter_ms", []), 50)
