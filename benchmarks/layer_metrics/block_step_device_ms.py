"""Median device time of one run of the block program
(``jit_paged_block_step`` on the trace's ``XLA Modules`` line) inside
the traced slice: what one step over the whole bucket costs the chip
(a block of positions a slot: denoising, committing or idle), whatever
the host does around it."""
from benchmarks.harness import program_trace


def read(run):
    return program_trace.program_ms(run, "paged_block_step")
