"""Share of its roofline the latent chunk kernel reaches inside the
chunk program: the least time the chip could take for ONE chunk run's
attention (the configuration's ``costs`` file, ``chunk_attention_cost``:
the operations of the (real query, key) pairs the window's chunks
attended, counter ``prefill_attended_pairs``, in the absorbed form, and
the chunks' contexts read once a layer, counter
``prefill_context_tokens``; both a run), the larger of operations over
the chip's published bf16 operations per second and bytes over its bytes
per second, over the device time of ``fused_latent_chunk`` in ONE run of
``jit_chunked_prefill_step``: the SUM of its calls, one a layer
(``harness/kernel_trace.py``).  The counters are the window's means and
the time the traced slice's, as in every share of a roofline here."""
from benchmarks.harness import cells, device, kernel_trace

KERNEL, PROGRAM = "fused_latent_chunk", "chunked_prefill_step"


def read(run):
    counters = run.get("counters") or {}
    costs = cells.config_module(run["config"], "costs")
    layers = run["config"].get("num_hidden_layers")
    calls = kernel_trace.kernel_call_seconds(run, KERNEL, PROGRAM)
    if not calls or not layers or len(calls) < layers \
            or costs is None or not hasattr(costs, "chunk_attention_cost"):
        return None
    flops, size = costs.chunk_attention_cost(run["config"], counters)
    if not flops:
        return None
    peaks = device.peaks(run["device"]["kind"])
    least_s = max(flops / peaks["bf16_flops_per_s"],
                  size / peaks["hbm_bytes_per_s"])
    run_s = sum(calls) / (len(calls) / layers)
    return 100.0 * least_s / run_s
