"""The device as JAX reports it, the one table of peaks, peak memory and
the count of compile requests."""
from __future__ import annotations

import jax

# Published peaks of one chip, keyed by ``device_kind``.  A device that
# is not here is an error, never a default.
PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of
    # HBM at 819 GB/s.  JAX names the chip "TPU v5 lite".
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add it "
            "to benchmarks/harness/device.py with its source")
    return PEAKS[device_kind]


def require_accelerator(chips: int) -> dict:
    """``{"platform", "kind", "count"}`` of the devices this run uses;
    exits when JAX finds no accelerator or fewer chips than the cell asks
    for (copied from ``chip_smoke.require_accelerator``)."""
    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise SystemExit(
            "benchmark: JAX found no accelerator (platform 'cpu'); a "
            "cell is measured on the chip and never falls back")
    if len(devices) < chips:
        raise SystemExit(
            f"benchmark: the cell needs {chips} chips, JAX reports "
            f"{len(devices)}")
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device (0 where the backend
    reports none, as the CPU's does)."""
    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class CompileCounter:
    """Counts compile requests (``backend_compile_duration`` fires once
    for each program JAX asks the backend for, a persistent-cache hit
    included), so a window can show that nothing compiled inside it."""

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, seconds, **_):
        if name == self._EVENT:
            self.count += 1
            self.seconds += seconds
