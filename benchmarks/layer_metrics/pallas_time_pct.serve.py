"""Share of the device's busy time spent in Pallas kernels (Mosaic
``tpu_custom_call`` events of the trace); each kernel is a row of
``breakdown.device_ops``."""
from benchmarks.harness.trace_reduce import pallas_pct as read  # noqa: F401
