"""Share of the device's busy time spent under the named scope
``lm_loss`` (the fused lm-head loss: the scan over token chunks, forward
and backward)."""
from benchmarks.harness import program_trace


def read(run):
    return program_trace.scope_pct(run, "lm_loss")
