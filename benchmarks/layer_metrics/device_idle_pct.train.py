"""Share of the traced slice in which no operation ran on the device:
1 - union of device-operation intervals / slice, from the profiler's
trace."""
from benchmarks.harness.trace_reduce import idle_pct as read  # noqa: F401
