"""Mean wait from ``submit()`` to first admission over the requests
admitted inside the window (``queue_wait_ns`` / ``admissions``, the
program's clock).  About zero in a closed loop with no more clients than
slots: it says that time to first token is not queueing there."""


def read(run):
    counters = run.get("counters") or {}
    admitted = counters.get("admissions")
    if not admitted or "queue_wait_ns" not in counters:
        return None
    return counters["queue_wait_ns"] / admitted / 1e6
