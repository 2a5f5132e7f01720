"""Programs the device may still have had to run when the host sat down
to wait for a result, a blocking read: the program's counter
``programs_behind_reads`` (at each read of a step program's result, the
programs dispatched since the read before it returned; the device runs
them in order) over ``blocking_reads``.  A loop that reads each step's
result before it dispatches the next reads 1 to 2; one that keeps the
device fed while the host works reads more."""


def read(run):
    counters = run.get("counters") or {}
    reads = counters.get("blocking_reads")
    if not reads or "programs_behind_reads" not in counters:
        return None
    return counters["programs_behind_reads"] / reads
