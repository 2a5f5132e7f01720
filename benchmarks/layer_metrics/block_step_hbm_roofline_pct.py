"""Share of the memory roofline the WHOLE block step reaches: the least
bytes a block step must read (the configuration's ``costs`` file,
``block_step_bytes``: every matrix outside the experts once, the experts
the step READ as the program's counters give them, and the cached keys
and values of the window's mean live context, ``block_context_tokens`` /
``block_steps``) over the chip's published bytes per second, over the
program's measured device time (as ``block_step_device_ms`` reads it).
A floor on bytes, so it cannot pass 100; the step is bound by memory:
at some hundred query tokens the experts' matrices are read for a few
rows each."""
from benchmarks.harness import cells, device, program_trace


def read(run):
    counters = run.get("counters") or {}
    steps = counters.get("block_steps")
    measured_ms = program_trace.program_ms(run, "paged_block_step")
    costs = cells.config_module(run["config"], "costs")
    if not steps or not measured_ms or costs is None \
            or not hasattr(costs, "block_step_bytes"):
        return None
    least_s = costs.block_step_bytes(
        run["config"], counters["block_context_tokens"] / steps, counters) \
        / device.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / (measured_ms / 1e3)
