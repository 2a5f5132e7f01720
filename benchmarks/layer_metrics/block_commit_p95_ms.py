"""95th percentile of the time between two consecutive blocks of one
request being complete at ``on_token`` (benchmark's clock; the kind's
``block_gaps_ms``): the cadence a streaming client of a block model
sees, where a token-to-token gap is mostly zero."""
from benchmarks.harness.stats import percentile


def read(run):
    return percentile(run.get("block_gaps_ms", []), 95)
