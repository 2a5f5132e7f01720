"""Share of the traced slice in which the device was idle while the host
was inside the engine's scheduling phases: ``serving::admit``,
``prefill_dispatch``, ``decode_prepare``, ``decode_dispatch`` and
``pool_sync`` (admission, block bookkeeping, building arguments, the
calls into the programs until they return handles)."""
from benchmarks.harness import program_trace


def read(run):
    return program_trace.idle_pct_inside(run, (
        "serving::admit", "serving::prefill_dispatch",
        "serving::decode_prepare", "serving::decode_dispatch",
        "serving::pool_sync"))
