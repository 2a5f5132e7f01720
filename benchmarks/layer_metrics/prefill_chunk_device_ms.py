"""Median device time of one run of the chunk program
(``jit_chunked_prefill_step`` on the trace's ``XLA Modules`` line)
inside the traced slice: one chunk of prompt at whatever context the
slice's prompts had reached."""
from benchmarks.harness import program_trace


def read(run):
    return program_trace.program_ms(run, "chunked_prefill_step")
