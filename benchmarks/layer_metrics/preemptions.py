"""Requests preempted inside the window (``ServingMetrics`` counter
``preemptions``, change over the window)."""


def read(run):
    counters = run.get("counters")
    return None if counters is None else counters["preemptions"]
