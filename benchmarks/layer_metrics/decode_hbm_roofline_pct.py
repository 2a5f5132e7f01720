"""Share of the memory roofline the decode program reaches: the least
bytes a decode step must read (``harness/bytes.py``, or the
configuration's own ``costs`` file: every matrix of the
resident model once, and the cached keys and values of the window's mean
live context, ``decode_context_tokens`` / ``decode_iterations``) over
the chip's published bytes per second, over the program's measured
device time (as ``decode_step_device_ms`` reads it).  A floor on bytes,
so it cannot pass 100; a decode step is bound by memory, not
arithmetic."""
from benchmarks.harness import bytes as step_bytes
from benchmarks.harness import device, program_trace


def read(run):
    counters = run.get("counters") or {}
    steps = counters.get("decode_iterations")
    context = counters.get("decode_context_tokens")
    measured_ms = program_trace.program_ms(run, "paged_decode_step")
    if not steps or context is None or not measured_ms:
        return None
    least_s = step_bytes.decode_step_bytes(
        run["config"], context / steps, counters) \
        / device.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / (measured_ms / 1e3)
