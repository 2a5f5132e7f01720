"""Median time to first token over the requests submitted inside the
window: the steadier companion of the end-to-end 95th percentile."""
from benchmarks.harness.stats import percentile


def read(run):
    return percentile(run.get("ttft_ms", []), 50)
