# lint-tpu: disable-file=L004 -- grandfathered direct jax use; new backend code belongs under core/ ops/ kernels/ static/ distributed/ (README: Repo lint)
"""paddle.device (reference: python/paddle/device/).

TPU is the accelerator; `cuda` names exist for API compatibility and map to
the accelerator backend (streams/events are no-ops under the XLA execution
model, where ordering is program order).
"""
from __future__ import annotations

import jax

from ..core.place import (  # noqa: F401
    CPUPlace, CUDAPinnedPlace, CUDAPlace, CustomPlace, IPUPlace, MLUPlace,
    NPUPlace, Place, TPUPlace, XPUPlace, device_count, get_device,
    is_compiled_with_cinn, is_compiled_with_cuda, is_compiled_with_ipu,
    is_compiled_with_mlu, is_compiled_with_npu, is_compiled_with_rocm,
    is_compiled_with_tpu, is_compiled_with_xpu, set_device,
)
from ..distributed.env import ParallelEnv  # noqa: F401


def get_all_custom_device_type():
    return []


def get_cudnn_version():
    return None


def get_all_device_type():
    return sorted({d.platform for d in jax.devices()})


def get_available_device():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_available_custom_device():
    return []


def synchronize(device=None):
    """Block until all queued device work finishes."""
    for d in jax.live_arrays():
        try:
            d.block_until_ready()
        except Exception:
            pass


# ------------------------------------------------------- memory stats
# Reference: paddle/fluid/memory/stats.h (HostMemoryStat* / DeviceMemoryStat*
# with peak tracking) and python/paddle/device/cuda max_memory_allocated.
# TPU-native: PJRT exposes per-device memory_stats() (bytes_in_use,
# peak_bytes_in_use); on backends without stats (CPU) we fall back to
# summing live arrays and track the peak at query time.
_peak_fallback = {"allocated": 0}


def _device_obj(device=None):
    if device is None:
        return jax.devices()[0]
    if isinstance(device, int):
        return jax.devices()[device]
    return device


def _mem_stats(device=None):
    d = _device_obj(device)
    try:
        return d.memory_stats()
    except Exception:
        return None


def memory_allocated(device=None) -> int:
    """Bytes currently held by live buffers on the device."""
    stats = _mem_stats(device)
    if stats:
        return int(stats.get("bytes_in_use", 0))
    total = 0
    for a in jax.live_arrays():
        try:
            total += a.nbytes
        except Exception:
            pass
    _peak_fallback["allocated"] = max(_peak_fallback["allocated"], total)
    return total


def max_memory_allocated(device=None) -> int:
    """High-water mark of allocated bytes (PJRT peak_bytes_in_use)."""
    stats = _mem_stats(device)
    if stats:
        return int(stats.get("peak_bytes_in_use",
                             stats.get("bytes_in_use", 0)))
    memory_allocated(device)  # refresh the fallback peak
    return _peak_fallback["allocated"]


def memory_reserved(device=None) -> int:
    """Bytes reserved by the allocator pool (PJRT bytes_reserved +
    in-use; CPU fallback: same as allocated)."""
    stats = _mem_stats(device)
    if stats:
        return int(stats.get("bytes_reserved", 0)
                   + stats.get("bytes_in_use", 0))
    return memory_allocated(device)


def max_memory_reserved(device=None) -> int:
    stats = _mem_stats(device)
    if stats:
        return int(stats.get("peak_bytes_reserved",
                             stats.get("peak_bytes_in_use", 0)))
    return max_memory_allocated(device)


def reset_peak_memory_stats(device=None):
    """Best-effort peak reset (PJRT peaks are monotonic; the fallback
    peak is ours to reset)."""
    _peak_fallback["allocated"] = 0


class Stream:
    """API-compat stream object: XLA orders work by program order, so
    streams are identity contexts (reference: phi stream objects)."""

    def __init__(self, device=None, priority=2):
        self.device = device

    def synchronize(self):
        synchronize()

    def wait_event(self, event):
        pass

    def wait_stream(self, stream):
        pass

    def record_event(self, event=None):
        return event or Event()

    def query(self):
        return True


class Event:
    def __init__(self, enable_timing=False, blocking=False, interprocess=False):
        pass

    def record(self, stream=None):
        pass

    def query(self):
        return True

    def synchronize(self):
        synchronize()


def current_stream(device=None):
    return Stream(device)


def set_stream(stream):
    return stream


class stream_guard:
    def __init__(self, stream):
        self.stream = stream

    def __enter__(self):
        return self.stream

    def __exit__(self, *a):
        return False


class cuda:
    """paddle.device.cuda compat namespace."""

    Stream = Stream
    Event = Event

    @staticmethod
    def device_count():
        return 0

    @staticmethod
    def synchronize(device=None):
        synchronize()

    @staticmethod
    def empty_cache():
        pass

    @staticmethod
    def max_memory_allocated(device=None):
        return max_memory_allocated(device)

    @staticmethod
    def memory_allocated(device=None):
        return memory_allocated(device)

    @staticmethod
    def memory_reserved(device=None):
        return memory_reserved(device)

    @staticmethod
    def max_memory_reserved(device=None):
        return max_memory_reserved(device)


    @staticmethod
    def current_stream(device=None):
        return Stream()

    @staticmethod
    def stream_guard(stream):
        return stream_guard(stream)

    @staticmethod
    def get_device_properties(device=None):
        import jax as _jax

        d = _device_obj(device)
        stats = _mem_stats(device) or {}

        class _Props:
            name = d.device_kind
            major, minor = 0, 0
            total_memory = stats.get("bytes_limit", 0)
            multi_processor_count = 1

            def __repr__(self):
                return (f"_CudaDeviceProperties(name='{self.name}', "
                        f"total_memory={self.total_memory})")

        return _Props()

    @staticmethod
    def get_device_name(device=None):
        return _device_obj(device).device_kind

    @staticmethod
    def get_device_capability(device=None):
        return (0, 0)
