"""Share of the bf16 MXU peak the chunk attention kernel reaches inside
the chunk program of a model with window and full layers: the least
operations ONE chunk run's attention must do (the configuration's
``costs`` file, ``chunk_attention_flops``: a head's q.k and p.v, ``2 (d
+ dv)`` operations, for every (real query, key) pair the window's chunks
attended, counters ``prefill_attended_pairs`` in the full layers and
``prefill_window_pairs`` in the window layers, a run) over the chip's
published bf16 operations per second, over the device time of
``fused_chunked_prefill`` in ONE run of ``jit_chunked_prefill_step``:
the SUM of its calls, one a layer (``harness/kernel_trace.py``).  The
counters are the window's means and the time the traced slice's, as in
every share of a roofline here."""
from benchmarks.harness import cells, device, kernel_trace

KERNEL, PROGRAM = "fused_chunked_prefill", "chunked_prefill_step"


def read(run):
    counters = run.get("counters") or {}
    costs = cells.config_module(run["config"], "costs")
    layers = run["config"].get("num_hidden_layers")
    calls = kernel_trace.kernel_call_seconds(run, KERNEL, PROGRAM)
    if not calls or not layers or len(calls) < layers \
            or costs is None or not hasattr(costs, "chunk_attention_flops"):
        return None
    flops = costs.chunk_attention_flops(run["config"], counters)
    if not flops:
        return None
    least_s = flops / device.peaks(run["device"]["kind"])["bf16_flops_per_s"]
    run_s = sum(calls) / (len(calls) / layers)
    return 100.0 * least_s / run_s
