"""Share of the memory roofline the grouped-experts kernel reaches
inside the one-token decode program: the least bytes one call (one
routed layer of one decode run) must move (the configuration's ``costs``
file, ``expert_kernel_call_bytes`` from the DECODE-ONLY counters
``experts_read_decode`` and ``expert_assignments_decode``: a decode run
of a few rows reads about half the experts, a chunk nearly all) over the
chip's published bytes per second, over the median device time of one
call of the kernel inside whole runs of ``jit_paged_decode_step``
(``harness/kernel_trace.py``)."""
from benchmarks.harness import cells, device, kernel_trace

KERNEL, PROGRAM = "moe_grouped_experts", "paged_decode_step"


def read(run):
    counters = run.get("counters") or {}
    costs = cells.config_module(run["config"], "costs")
    call_ms = kernel_trace.kernel_call_ms(run, KERNEL, PROGRAM)
    if not call_ms or costs is None \
            or not hasattr(costs, "expert_kernel_call_bytes") \
            or "experts_read_decode" not in counters:
        return None
    least_bytes = costs.expert_kernel_call_bytes(run["config"], counters)
    if not least_bytes:
        return None
    least_s = least_bytes \
        / device.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / (call_ms / 1e3)
