"""Median device time of one run of the decode program
(``jit_paged_decode_step`` on the trace's ``XLA Modules`` line) inside
the traced slice: what one decode step over the whole bucket costs the
chip, whatever the host does around it."""
from benchmarks.harness import program_trace


def read(run):
    return program_trace.program_ms(run, "paged_decode_step")
