"""95th percentile of time to first token over the requests submitted
inside the window (``submit()`` to the first ``on_token``, benchmark's
clock).  A closed loop at today's speed sends some tens of requests a
window, so this tail swings from run to run: it stands here, unbounded,
until an open-loop cell or a faster engine gives it the hundreds of
samples an end-to-end bound needs (PERF.md section 2)."""
from benchmarks.harness.stats import percentile


def read(run):
    return percentile(run.get("ttft_ms", []), 95)
