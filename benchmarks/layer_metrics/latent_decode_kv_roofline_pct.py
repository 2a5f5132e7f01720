"""Share of the memory roofline the latent decode kernel reaches inside
the decode program: the cached latent entries one decode run's walks
must cover (the configuration's ``costs`` file, ``decode_kv_bytes``:
every live position in every layer at the PUBLISHED entry's bytes,
whatever the pool's layout pads it to) over the chip's published bytes
per second, over the device time of ``fused_latent_decode`` in ONE run
of ``jit_paged_decode_step``: the SUM of its calls, one a layer
(``harness/kernel_trace.py``: the kernel's own events inside whole
runs)."""
from benchmarks.harness import cells, device, kernel_trace

KERNEL, PROGRAM = "fused_latent_decode", "paged_decode_step"


def read(run):
    counters = run.get("counters") or {}
    costs = cells.config_module(run["config"], "costs")
    layers = run["config"].get("num_hidden_layers")
    calls = kernel_trace.kernel_call_seconds(run, KERNEL, PROGRAM)
    if not calls or not layers or len(calls) < layers \
            or costs is None or not hasattr(costs, "decode_kv_bytes"):
        return None
    least_bytes = costs.decode_kv_bytes(run["config"], counters)
    if not least_bytes:
        return None
    run_s = sum(calls) / (len(calls) / layers)
    least_s = least_bytes \
        / device.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / run_s
