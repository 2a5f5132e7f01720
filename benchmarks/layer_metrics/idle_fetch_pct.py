"""Share of the traced slice in which the device was idle while the host
was inside ``serving::decode_fetch`` or ``serving::first_token``: a
program has ended and its logits are on their way to the host.  With
``idle_sample_pct``, ``idle_schedule_pct`` and the gaps outside
``Engine.step()`` it sums to ``device_idle_pct.serve`` of the same run."""
from benchmarks.harness import program_trace


def read(run):
    return program_trace.idle_pct_inside(
        run, ("serving::decode_fetch", "serving::first_token"))
