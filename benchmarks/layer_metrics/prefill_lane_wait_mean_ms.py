"""Mean wait from first admission to the dispatch of a request's first
prefill chunk (``lane_wait_ns`` / ``admissions``, the program's clock):
the time a request spends behind other requests' chunks on the
one-chunk-an-iteration prefill lane."""


def read(run):
    counters = run.get("counters") or {}
    admitted = counters.get("admissions")
    if not admitted or "lane_wait_ns" not in counters:
        return None
    return counters["lane_wait_ns"] / admitted / 1e6
