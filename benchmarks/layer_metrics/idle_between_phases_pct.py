"""Share of the traced slice in which the device was idle while the host
was inside ``Engine.step()`` and in none of its eight phases: gaps whose
innermost span is ``serving::step`` itself (the watchdogs' bookkeeping,
the calls between two phases, the step's account).  With
``idle_fetch_pct``, ``idle_sample_pct``, ``idle_schedule_pct`` and the
gaps outside ``Engine.step()`` it sums to ``device_idle_pct.serve`` of
the same run.  ``None`` where the program has no such span."""
from benchmarks.harness import program_trace

SPAN = "serving::step"


def read(run):
    trace = program_trace.load(run)
    if trace is None or not any(name == SPAN for _, _, name, _ in trace.spans):
        return None
    return program_trace.idle_pct_inside(run, (SPAN,))
