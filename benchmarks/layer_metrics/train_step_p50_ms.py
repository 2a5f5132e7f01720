"""Median time from one step's end to the next's (``Model.fit``'s loop:
batch to the device, one jitted train step, the loss read on the host)
on the benchmark's callback clock."""
from benchmarks.harness.stats import percentile


def read(run):
    return percentile(run.get("step_ms", []), 50)
