"""Model FLOP/s utilization of the training window: operations a trained
token needs (``harness/flops.py``: matrices x6 and causal attention, no
recompute, no embedding lookup) x tokens/s over the chip's published
bf16 peak.  An end-to-end utilization, not a kernel's roofline share."""
from benchmarks.harness import device, flops


def read(run):
    if not run.get("tokens") or "sequence" not in run:
        return None
    per_token = flops.model_flops_per_token(run["config"], run["sequence"])
    peak = device.peaks(run["device"]["kind"])["bf16_flops_per_s"]
    rate = per_token * run["tokens"] / run["seconds"]
    return 100.0 * rate / (peak * run["chips"])
