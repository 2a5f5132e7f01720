"""Share of the memory roofline the grouped-experts kernel reaches
inside the block program: the least bytes one call (one layer of one
step) must move (the configuration's ``costs`` file,
``expert_kernel_call_bytes``: the experts the call READ, as the
program's counters give them, and one row in and one row out for every
assignment) over the chip's published bytes per second, over the median
device time of one call of the kernel (``harness/kernel_trace.py``: its
own events in the trace, inside whole runs of ``jit_paged_block_step``).
The counters average over block steps and prefill chunks; both read
nearly every expert, and a chunk's rows are a few per cent of its
bytes."""
from benchmarks.harness import cells, device, kernel_trace

KERNEL, PROGRAM = "moe_grouped_experts", "paged_block_step"


def read(run):
    counters = run.get("counters") or {}
    costs = cells.config_module(run["config"], "costs")
    call_ms = kernel_trace.kernel_call_ms(run, KERNEL, PROGRAM)
    if not call_ms or costs is None \
            or not hasattr(costs, "expert_kernel_call_bytes"):
        return None
    least_bytes = costs.expert_kernel_call_bytes(run["config"], counters)
    if not least_bytes:
        return None
    least_s = least_bytes \
        / device.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / (call_ms / 1e3)
