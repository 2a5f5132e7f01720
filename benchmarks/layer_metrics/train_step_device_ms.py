"""Median device time of one run of the train program
(``jit_train_step`` on the trace's ``XLA Modules`` line) inside the
traced slice; ``train_step_p50_ms`` is the same step on the host's
clock, with the loop around it."""
from benchmarks.harness import program_trace


def read(run):
    return program_trace.program_ms(run, "train_step")
