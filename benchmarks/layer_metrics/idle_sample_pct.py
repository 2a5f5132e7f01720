"""Share of the traced slice in which the device was idle while the host
was inside ``serving::sample_emit``: the per-slot argmax over the
logits, the tokens' bookkeeping, ``on_token`` and retirement, all before
the next program can be dispatched."""
from benchmarks.harness import program_trace


def read(run):
    return program_trace.idle_pct_inside(run, ("serving::sample_emit",))
