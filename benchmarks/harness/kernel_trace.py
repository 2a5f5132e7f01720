"""Device time of ONE call of a named kernel, read out of the trace a
run wrote.  ``program_trace`` sums an operation's events over the slice;
a kernel's share of its roofline needs the time of a call, so this
counts the events themselves: those of the ``XLA Ops`` line whose
instruction is named after the kernel (a Pallas kernel's is named after
its ``pallas_call``), whole inside a whole run of the given program
inside the slice."""
from __future__ import annotations

from . import program_trace, trace_reduce
from .stats import percentile

_calls = {}     # (path, kernel, program) -> [seconds]


def kernel_call_seconds(run, kernel: str, program: str):
    """Device seconds of every whole call of ``kernel`` inside a whole
    run of ``program`` in the slice (first device); ``None`` without
    this run's trace, ``[]`` where the trace holds no such call."""
    trace = program_trace.load(run)
    if trace is None:
        return None
    key = (run["trace_path"], kernel, program)
    if key not in _calls:
        events = program_trace.read_events(run["trace_path"])
        planes = sorted({p for p, ln, _, _, _, _ in events
                         if trace_reduce.is_device_op(p, ln)})
        runs = sorted((s, s + d) for p, ln, n, s, d, _ in events
                      if p == planes[0] and ln == "XLA Modules"
                      and program_trace.program_name(n) == program
                      and s >= trace.lo and s + d <= trace.hi)
        found = []
        for p, ln, n, s, d, _ in events:
            if p != planes[0] or not trace_reduce.is_device_op(p, ln):
                continue
            if trace_reduce.short_name(n) not in (
                    kernel, trace_reduce.PALLAS_PREFIX + kernel):
                continue
            if any(lo <= s and s + d <= hi for lo, hi in runs):
                found.append(d * 1e-9)
        _calls[key] = found
    return _calls[key]


def kernel_call_ms(run, kernel: str, program: str):
    """Median device milliseconds of one call; ``None`` where there is
    nothing to read."""
    calls = kernel_call_seconds(run, kernel, program)
    return 1e3 * percentile(calls, 50) if calls else None
