# lint-tpu: disable-file=L004 -- grandfathered direct jax use; new backend code belongs under core/ ops/ kernels/ static/ distributed/ (README: Repo lint)
"""paddle.profiler (reference: python/paddle/profiler/profiler.py:270 +
platform/profiler/ host tracer + CUPTI).

TPU-native: host ranges recorded with perf_counter_ns (the HostTraceLevel
analog); device activity comes from jax.profiler (XLA/Xprof) traces.  Export
keeps the chrome://tracing JSON format the reference emits
(chrometracing_logger.cc).  A ``RecordEvent`` is also a
``jax.profiler.TraceAnnotation``: it shows in any XLA trace, session or not.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from enum import Enum
from typing import Callable, List, Optional

import jax

__all__ = ["Profiler", "ProfilerState", "ProfilerTarget", "RecordEvent",
           "make_scheduler", "export_chrome_tracing", "load_profiler_result",
           "current_profiler"]


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1
    TPU = 2
    CUSTOM_DEVICE = 3


class _HostEventRecorder:
    """Lock-free-ish per-thread buffers (reference: host_event_recorder.h)."""

    def __init__(self):
        self._local = threading.local()
        self._all_buffers = []
        self._lock = threading.Lock()
        self._native = None  # None = undecided, False = python fallback

    def _buffer(self):
        buf = getattr(self._local, "buf", None)
        if buf is None:
            buf = []
            self._local.buf = buf
            with self._lock:
                # OS thread id, same namespace as the native tracer's
                # SYS_gettid, so both sources merge per-thread.
                self._all_buffers.append((threading.get_native_id(), buf))
        return buf

    def record(self, name, start_ns, end_ns, category="host"):
        # Prefer the native recorder (core/native/host_tracer.cc) for the
        # default category: the hot path is a C++ clock read + push.  The
        # native buffer carries no category, so non-host events stay on the
        # Python buffer.  The native-vs-fallback decision is resolved once.
        if self._native is None:
            from . import host_tracer

            self._native = host_tracer if host_tracer.available() else False
        if self._native and category == "host":
            self._native.emit(name, start_ns, end_ns)
        else:
            self._buffer().append((name, start_ns, end_ns, category))

    def drain(self):
        # Only touch the native tracer if it was actually used for
        # recording — host_tracer.drain() JIT-compiles the C++ library on
        # first use, which must not be triggered by merely stopping a
        # session that recorded nothing natively.
        out = []
        if self._native:
            from . import host_tracer

            out = list(host_tracer.drain())
        else:
            from . import host_tracer

            # events recorded through host_tracer's pure-Python fallback
            # (direct begin/end/emit users while the native lib is
            # unavailable) merge here; fallback_active() short-circuits
            # before _load(), so this never triggers the JIT compile
            if host_tracer.fallback_active():
                out = list(host_tracer.drain())
        with self._lock:
            for tid, buf in self._all_buffers:
                out.extend((tid,) + e for e in buf)
                buf.clear()
        return out


_recorder = _HostEventRecorder()
_active_profiler: Optional["Profiler"] = None


def current_profiler() -> Optional["Profiler"]:
    """The active Profiler session, or None."""
    return _active_profiler


class RecordEvent:
    """Annotated host range (reference: event_tracing.h RecordEvent): the
    program's one span.

    ``begin`` opens a ``jax.profiler.TraceAnnotation``, so the range
    lands in ANY ``jax.profiler`` trace that is recording, on line
    ``python3`` of plane ``/host:CPU`` and on the clock of the device
    planes, with ``metadata`` as the event's stats; while no trace
    records it costs a few hundred nanoseconds.  The range is also
    appended to the host recorder, for the chrome export, but only while
    a :class:`Profiler` session is active: used on a hot path outside a
    session it buffers nothing.  Keep ``name`` one of a fixed set; what
    varies (a request id, a size) belongs in ``metadata``."""

    __slots__ = ("name", "_metadata", "_annotation", "_start")

    def __init__(self, name: str, event_type=None, **metadata):
        self.name = name
        self._metadata = metadata
        self._annotation = None
        self._start = None

    def begin(self):
        self._annotation = jax.profiler.TraceAnnotation(self.name,
                                                        **self._metadata)
        self._annotation.__enter__()
        if _active_profiler is not None:
            self._start = time.perf_counter_ns()

    def end(self):
        if self._start is not None:
            _recorder.record(self.name, self._start, time.perf_counter_ns())
            self._start = None
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *a):
        self.end()
        return False


def make_scheduler(closed: int = 0, ready: int = 0, record: int = 1,
                   repeat: int = 0, skip_first: int = 0) -> Callable[[int],
                                                                     ProfilerState]:
    period = closed + ready + record

    def scheduler(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * period:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    def handler(prof: "Profiler"):
        os.makedirs(dir_name, exist_ok=True)
        fname = os.path.join(
            dir_name, f"{worker_name or 'worker'}_{int(time.time())}.json")
        prof._export_chrome(fname)
        return fname

    return handler


class Profiler:
    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, record_shapes=False, profile_memory=False,
                 with_flops=False, emit_nvtx=False):
        self.targets = targets or [ProfilerTarget.CPU]
        if scheduler is None:
            self.scheduler = lambda step: ProfilerState.RECORD
        elif isinstance(scheduler, tuple):
            lo, hi = scheduler
            self.scheduler = lambda step: (
                ProfilerState.RECORD if lo <= step < hi
                else ProfilerState.CLOSED)
        else:
            self.scheduler = scheduler
        self.on_trace_ready = on_trace_ready
        self.step_num = 0
        self.state = ProfilerState.CLOSED
        self.events: List[tuple] = []
        self._step_times: List[float] = []
        self._last_step_t = None
        self._jax_trace_dir = None
        # [(host_anchor_ns, [chrome events])] — one segment per record
        # window, each rebased with ITS OWN anchor at export
        self._device_segments: List[tuple] = []
        self._device_anchor_ns = None

    # -- lifecycle
    def start(self):
        self.state = self.scheduler(self.step_num)
        self._maybe_start_device_trace()
        self._last_step_t = time.perf_counter()
        global _active_profiler
        _active_profiler = self

    def stop(self):
        self.events.extend(_recorder.drain())
        self._maybe_stop_device_trace()
        if self.on_trace_ready is not None:
            self.on_trace_ready(self)
        global _active_profiler
        _active_profiler = None
        self.state = ProfilerState.CLOSED

    def step(self, num_samples=None):
        now = time.perf_counter()
        if self._last_step_t is not None:
            self._step_times.append(now - self._last_step_t)
        self._last_step_t = now
        self.events.extend(_recorder.drain())
        self.step_num += 1
        new_state = self.scheduler(self.step_num)
        if new_state != self.state:
            if new_state == ProfilerState.CLOSED:
                self._maybe_stop_device_trace()
            elif self.state == ProfilerState.CLOSED:
                self._maybe_start_device_trace()
            self.state = new_state

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *a):
        self.stop()
        return False

    # -- device (XLA) trace via jax.profiler
    def _maybe_start_device_trace(self):
        if ProfilerTarget.TPU in self.targets and \
                self.state in (ProfilerState.RECORD,
                               ProfilerState.RECORD_AND_RETURN):
            import tempfile

            self._jax_trace_dir = tempfile.mkdtemp(prefix="paddle_tpu_trace_")
            try:
                jax.profiler.start_trace(self._jax_trace_dir)
                self._device_anchor_ns = time.perf_counter_ns()
            except Exception:
                self._jax_trace_dir = None

    def _maybe_stop_device_trace(self):
        if self._jax_trace_dir is not None:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._collect_device_events(self._jax_trace_dir)
            self._jax_trace_dir = None

    def _collect_device_events(self, trace_dir):
        """Pull the XLA profiler's chrome events (the *.trace.json.gz the
        PJRT profiler session writes next to the xplane.pb) into this
        profiler, so export() emits ONE file with host + device lanes —
        the reference's merged event tree (platform/profiler/
        chrometracing_logger.cc) instead of two disconnected dirs."""
        import glob
        import gzip

        events = []
        for path in glob.glob(os.path.join(
                trace_dir, "plugins", "profile", "*", "*.trace.json.gz")):
            try:
                with gzip.open(path, "rt") as f:
                    payload = json.load(f)
            except Exception:
                continue
            events.extend(payload.get("traceEvents", []))
        if events:
            self._device_segments.append((self._device_anchor_ns, events))

    # -- reporting
    def _export_chrome(self, path):
        trace_events = []
        host_pid = os.getpid()
        for tid, name, start_ns, end_ns, cat in self.events:
            trace_events.append({
                "name": name, "cat": cat, "ph": "X",
                "ts": start_ns / 1000.0, "dur": (end_ns - start_ns) / 1000.0,
                "pid": host_pid, "tid": tid,
            })
        trace_events.append({
            "ph": "M", "name": "process_name", "pid": host_pid,
            "args": {"name": "host (paddle_tpu ranges)"}})
        # device lanes ride under their own pids, rebased PER RECORD
        # WINDOW so the two clock domains land on one timeline: each
        # segment's earliest timestamp is pinned to the host
        # perf_counter moment ITS start_trace returned (a global shift
        # would stack multi-window traces on top of each other)
        pid_off = host_pid + 100000
        for anchor_ns, events in self._device_segments:
            ts_events = [e for e in events if "ts" in e]
            shift = 0.0
            if ts_events and anchor_ns is not None:
                shift = (anchor_ns / 1000.0
                         - min(float(e["ts"]) for e in ts_events))
            for e in events:
                e = dict(e)
                if "ts" in e:
                    e["ts"] = float(e["ts"]) + shift
                if "pid" in e:
                    try:
                        e["pid"] = int(e["pid"]) + pid_off
                    except (TypeError, ValueError):
                        pass
                trace_events.append(e)
        with open(path, "w") as f:
            json.dump({"traceEvents": trace_events}, f)
        return path

    def export(self, path, format="json"):
        return self._export_chrome(path)

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        agg = {}
        for tid, name, start_ns, end_ns, cat in self.events:
            d = agg.setdefault(name, [0, 0.0, 0.0, float("inf")])
            dur = (end_ns - start_ns) / 1e6
            d[0] += 1
            d[1] += dur
            d[2] = max(d[2], dur)
            d[3] = min(d[3], dur)
        # SortedKeys: host-range stats (the GPU* keys of the reference map
        # onto the same host table here — device timing lives in the
        # Xplane trace jax.profiler captures)
        sort_fns = {
            None: lambda kv: -kv[1][1],
            SortedKeys.CPUTotal: lambda kv: -kv[1][1],
            SortedKeys.GPUTotal: lambda kv: -kv[1][1],
            SortedKeys.CPUAvg: lambda kv: -(kv[1][1] / kv[1][0]),
            SortedKeys.GPUAvg: lambda kv: -(kv[1][1] / kv[1][0]),
            SortedKeys.CPUMax: lambda kv: -kv[1][2],
            SortedKeys.GPUMax: lambda kv: -kv[1][2],
            SortedKeys.CPUMin: lambda kv: kv[1][3],
            SortedKeys.GPUMin: lambda kv: kv[1][3],
        }
        key_fn = sort_fns.get(sorted_by, sort_fns[None])
        lines = [f"{'Name':<40}{'Calls':>8}{'Total(ms)':>12}{'Avg(ms)':>12}"
                 f"{'Max(ms)':>12}{'Min(ms)':>12}"]
        for name, (calls, total, mx, mn) in sorted(agg.items(), key=key_fn):
            lines.append(f"{name:<40}{calls:>8}{total:>12.3f}"
                         f"{total / calls:>12.3f}{mx:>12.3f}{mn:>12.3f}")
        if self._step_times:
            import numpy as np

            lines.append(f"steps: {len(self._step_times)}, avg "
                         f"{np.mean(self._step_times) * 1000:.2f}ms")
        report = "\n".join(lines)
        print(report)
        return report


def load_profiler_result(filename):
    with open(filename) as f:
        return json.load(f)


class benchmark:
    """paddle.profiler.benchmark timer (ips) analog."""

    def __init__(self):
        self._times = []
        self._t = None

    def begin(self):
        self._t = time.perf_counter()

    def end(self, num_samples=1):
        if self._t is not None:
            self._times.append((time.perf_counter() - self._t, num_samples))

    def ips(self):
        total_t = sum(t for t, _ in self._times)
        total_n = sum(n for _, n in self._times)
        return total_n / total_t if total_t else 0.0


class SortedKeys:
    """Summary-table sort orders (reference: profiler/profiler_statistic.py
    SortedKeys enum)."""

    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3
    GPUTotal = 4
    GPUAvg = 5
    GPUMax = 6
    GPUMin = 7


def export_protobuf(dir_name: str, worker_name: str = None):
    """Post-run exporter hook (reference: profiler.export_protobuf writes
    the profiler result protobuf).  The device half of our trace already
    lands as Xplane protobufs under jax.profiler's log dir; the host
    ranges export as chrome-trace JSON (the reference's .pb wire format
    is paddle-internal) — same behavior as export_chrome_tracing,
    including the timestamp suffix that keeps runs from clobbering each
    other."""
    return export_chrome_tracing(dir_name, worker_name)
