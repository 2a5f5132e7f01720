"""Multi-process cluster bootstrap (reference: paddle.distributed.launch +
python/paddle/distributed/parallel.py:91 init flow).

``initialize_cluster`` wraps ``jax.distributed.initialize()`` with

* env-var autodiscovery (``PADDLE_TPU_COORDINATOR`` / ``_NUM_PROCESSES`` /
  ``_PROCESS_ID``, falling back to the reference's ``PADDLE_TRAINER_*``
  triple), so launchers only have to export a handful of variables;
* idempotent re-entry guards — a second call with compatible arguments is
  a no-op returning the live :class:`ClusterInfo`; a conflicting call
  raises instead of silently re-initializing a different topology;
* the CPU-emulation details that make a *real* multi-controller runtime
  run in CI with no TPU: gloo TCP collectives must be selected before the
  CPU backend is created (the env var alone does not bind on this jaxlib;
  ``jax.config.update("jax_cpu_collectives_implementation", "gloo")`` is
  required), and ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
  gives each process N emulated local devices.

``spawn_local(n, target)`` forks N ``JAX_PLATFORMS=cpu`` subprocesses
pre-wired to rendezvous on a free localhost port — the harness tier-1 CI
and ``examples/elastic_train.py`` use to exercise process-death chaos.

``ProcessContext`` is the small seam the sharded checkpointer and the
S209 cross-process aggregation are written against: ``index``/``count``
plus a named ``barrier``.  ``cluster_context()`` returns the live one;
``emulated_process_context(index, count)`` overrides it in-process so
protocol tests can play both sides of a 2-process save sequentially
without paying for subprocesses.
"""
from __future__ import annotations

import dataclasses
import os
import socket
import subprocess
import sys
import time
import warnings
from typing import Callable, Dict, List, Optional, Sequence

__all__ = [
    "ClusterInfo",
    "ProcessContext",
    "barrier",
    "cluster_context",
    "emulated_process_context",
    "initialize_cluster",
    "is_coordinator",
    "process_count",
    "process_index",
    "shutdown_cluster",
    "spawn_local",
]

# -- env autodiscovery ------------------------------------------------------

_ENV_COORD = ("PADDLE_TPU_COORDINATOR", "JAX_COORDINATOR_ADDRESS")
_ENV_NPROC = ("PADDLE_TPU_NUM_PROCESSES", "JAX_NUM_PROCESSES",
              "PADDLE_TRAINERS_NUM")
_ENV_PID = ("PADDLE_TPU_PROCESS_ID", "JAX_PROCESS_ID", "PADDLE_TRAINER_ID")

_DEFAULT_BARRIER_TIMEOUT_S = 120.0


def _env_first(names: Sequence[str]) -> Optional[str]:
    for n in names:
        v = os.environ.get(n)
        if v not in (None, ""):
            return v
    return None


@dataclasses.dataclass(frozen=True)
class ClusterInfo:
    """What ``initialize_cluster`` resolved and activated."""

    coordinator: Optional[str]
    num_processes: int
    process_id: int
    local_device_count: int
    cpu_collectives: Optional[str] = None

    @property
    def multiprocess(self) -> bool:
        return self.num_processes > 1


_CLUSTER: Optional[ClusterInfo] = None


def _jax():
    import jax

    return jax


def initialize_cluster(coordinator: Optional[str] = None,
                       num_processes: Optional[int] = None,
                       process_id: Optional[int] = None,
                       *,
                       cpu_collectives: str = "gloo",
                       initialization_timeout: int = 60) -> ClusterInfo:
    """Join (or declare) the multi-controller runtime.

    Arguments default from the environment (``PADDLE_TPU_COORDINATOR``,
    ``PADDLE_TPU_NUM_PROCESSES``, ``PADDLE_TPU_PROCESS_ID``, then the
    ``JAX_*`` / ``PADDLE_TRAINER_*`` equivalents).  With no coordinator
    and no multi-process env, this records a single-process cluster and
    never touches ``jax.distributed`` — safe to call unconditionally at
    program start.

    Re-entry: a second call that agrees with the live cluster returns the
    existing :class:`ClusterInfo`; a disagreeing call raises
    ``RuntimeError`` (a process cannot belong to two clusters).
    """
    global _CLUSTER

    coordinator = coordinator or _env_first(_ENV_COORD)
    if num_processes is None:
        v = _env_first(_ENV_NPROC)
        num_processes = int(v) if v is not None else None
    if process_id is None:
        v = _env_first(_ENV_PID)
        process_id = int(v) if v is not None else None

    if num_processes is None:
        num_processes = 1 if coordinator is None else None
    if num_processes == 1 and process_id is None:
        process_id = 0

    if _CLUSTER is not None:
        same = ((coordinator is None or coordinator == _CLUSTER.coordinator)
                and (num_processes is None
                     or num_processes == _CLUSTER.num_processes)
                and (process_id is None or process_id == _CLUSTER.process_id))
        if not same:
            raise RuntimeError(
                f"initialize_cluster re-entered with conflicting topology: "
                f"live={_CLUSTER} requested=(coordinator={coordinator!r}, "
                f"num_processes={num_processes}, process_id={process_id})")
        return _CLUSTER

    jax = _jax()
    if num_processes == 1:
        _CLUSTER = ClusterInfo(coordinator=None, num_processes=1,
                               process_id=0,
                               local_device_count=len(jax.local_devices()))
        _export_cluster_gauges(_CLUSTER)
        return _CLUSTER

    if coordinator is None or num_processes is None or process_id is None:
        raise ValueError(
            "multi-process initialize_cluster needs coordinator, "
            "num_processes and process_id (set PADDLE_TPU_COORDINATOR / "
            "PADDLE_TPU_NUM_PROCESSES / PADDLE_TPU_PROCESS_ID or pass them "
            f"explicitly); got coordinator={coordinator!r}, "
            f"num_processes={num_processes}, process_id={process_id}")

    applied_collectives = None
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if cpu_collectives and ("cpu" in platforms or platforms == ""):
        # must land before the CPU client exists; if the backend is
        # already up this is a silent no-op and collectives will fail
        # with "Multiprocess computations aren't implemented on the CPU
        # backend" — surface that early.
        if _backends_initialized():
            warnings.warn(
                "initialize_cluster: the XLA backend is already "
                "initialized; CPU collectives implementation "
                f"'{cpu_collectives}' cannot be applied. Call "
                "initialize_cluster before any jax.devices()/computation.",
                RuntimeWarning, stacklevel=2)
        else:
            jax.config.update("jax_cpu_collectives_implementation",
                              cpu_collectives)
            applied_collectives = cpu_collectives

    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id,
                               initialization_timeout=initialization_timeout)
    _CLUSTER = ClusterInfo(coordinator=coordinator,
                           num_processes=num_processes,
                           process_id=process_id,
                           local_device_count=len(jax.local_devices()),
                           cpu_collectives=applied_collectives)
    _export_cluster_gauges(_CLUSTER)
    return _CLUSTER


def _backends_initialized() -> bool:
    # private, but there is no public way to ask without initializing;
    # on the installed jax 0.9.0 ``_backends`` is a dict keyed by
    # platform, empty until the first backend comes up
    from jax._src import xla_bridge

    return bool(xla_bridge._backends)


def _export_cluster_gauges(info: ClusterInfo) -> None:
    try:
        from ..observability import registry as _obsreg

        reg = _obsreg.get_registry()
        reg.gauge("cluster_process_count",
                  "processes in the multi-controller runtime",
                  ).set(info.num_processes)
        reg.gauge("cluster_process_index",
                  "this process's index in the cluster").set(info.process_id)
        reg.gauge("cluster_local_devices",
                  "devices addressable by this process",
                  ).set(info.local_device_count)
    except Exception:
        pass


def shutdown_cluster() -> None:
    """Tear down ``jax.distributed`` (if up) and forget the cluster."""
    global _CLUSTER
    if _CLUSTER is not None and _CLUSTER.multiprocess:
        try:
            _jax().distributed.shutdown()
        except Exception:
            pass
    _CLUSTER = None


# -- process context --------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ProcessContext:
    """index/count plus a named barrier — the seam sharded checkpointing
    and cross-process reconciliation are written against."""

    index: int
    count: int
    barrier_fn: Optional[Callable[[str], None]] = None

    def barrier(self, name: str,
                timeout_s: float = _DEFAULT_BARRIER_TIMEOUT_S) -> None:
        if self.count <= 1:
            return
        if self.barrier_fn is not None:
            self.barrier_fn(name)
            return
        _distributed_barrier(name, timeout_s)

    @property
    def is_coordinator(self) -> bool:
        return self.index == 0


_EMULATED: List[ProcessContext] = []


class emulated_process_context:
    """Pretend to be process ``index`` of ``count`` inside one process.

    Barriers no-op (protocol tests drive the per-process save calls
    sequentially, non-coordinators first, coordinator last — the same
    ordering the real barrier enforces).  Nests; the innermost wins.
    """

    def __init__(self, index: int, count: int,
                 barrier: Optional[Callable[[str], None]] = None):
        if not 0 <= index < count:
            raise ValueError(f"index {index} out of range for count {count}")
        self._ctx = ProcessContext(index=index, count=count,
                                   barrier_fn=barrier or (lambda name: None))

    def __enter__(self) -> ProcessContext:
        _EMULATED.append(self._ctx)
        return self._ctx

    def __exit__(self, *exc) -> None:
        _EMULATED.pop()


def cluster_context() -> ProcessContext:
    """The live process context: emulation override if active, else the
    real runtime (jax.process_index/count)."""
    if _EMULATED:
        return _EMULATED[-1]
    jax = _jax()
    try:
        idx, cnt = jax.process_index(), jax.process_count()
    except Exception:
        idx, cnt = 0, 1
    return ProcessContext(index=idx, count=cnt)


def process_index() -> int:
    return cluster_context().index


def process_count() -> int:
    return cluster_context().count


def is_coordinator() -> bool:
    return cluster_context().index == 0


def barrier(name: str,
            timeout_s: float = _DEFAULT_BARRIER_TIMEOUT_S) -> None:
    """Block until every process reaches the same named barrier.

    Uses the distributed-runtime coordination service when available
    (which — unlike a psum over devices — carries a timeout, so a dead
    peer turns into an exception instead of a hang), falling back to
    ``sync_global_devices``.
    """
    cluster_context().barrier(name, timeout_s)


def _distributed_barrier(name: str, timeout_s: float) -> None:
    jax = _jax()
    client = None
    try:
        from jax._src import distributed as _dist

        client = _dist.global_state.client
    except Exception:
        client = None
    if client is not None:
        client.wait_at_barrier(name, timeout_in_ms=int(timeout_s * 1000))
        return
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(name)


# -- local spawn harness ----------------------------------------------------

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def spawn_local(num_processes: int,
                argv: Sequence[str],
                *,
                devices_per_process: int = 1,
                env: Optional[Dict[str, str]] = None,
                timeout_s: float = 600.0,
                grace_s: float = 10.0,
                stream_output: bool = True) -> List[int]:
    """Launch ``num_processes`` copies of ``argv`` as an emulated CPU
    cluster and supervise them; returns the per-process exit codes.

    Each child gets ``JAX_PLATFORMS=cpu``, ``XLA_FLAGS`` forcing
    ``devices_per_process`` host devices, and the ``PADDLE_TPU_*`` triple
    pointing at a fresh localhost coordinator — so a child only has to
    call :func:`initialize_cluster` (no arguments) to join.

    Supervision mirrors a TPU fleet controller: the first child to die
    takes the job with it — remaining children are terminated after
    ``grace_s`` (a dead peer would otherwise hang every collective).

    A CPU-only harness by design: a chip belongs to one process, so
    the children are pinned to the CPU backend whatever the parent
    runs on, and this is never the way to reach an accelerator (one
    process drives all of a host's chips).
    """
    if num_processes < 1:
        raise ValueError("num_processes must be >= 1")
    port = _free_port()
    base = dict(os.environ)
    base.update(env or {})
    base["JAX_PLATFORMS"] = "cpu"
    base["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices_per_process}")
    base["PADDLE_TPU_COORDINATOR"] = f"127.0.0.1:{port}"
    base["PADDLE_TPU_NUM_PROCESSES"] = str(num_processes)

    procs: List[subprocess.Popen] = []
    for i in range(num_processes):
        child_env = dict(base)
        child_env["PADDLE_TPU_PROCESS_ID"] = str(i)
        procs.append(subprocess.Popen(
            list(argv), env=child_env,
            stdout=None if stream_output else subprocess.DEVNULL,
            stderr=None if stream_output else subprocess.DEVNULL))

    deadline = time.monotonic() + timeout_s
    rcs: List[Optional[int]] = [None] * num_processes
    try:
        while any(rc is None for rc in rcs):
            for i, p in enumerate(procs):
                if rcs[i] is None:
                    rcs[i] = p.poll()
            exited = [rc for rc in rcs if rc is not None]
            if any(rc != 0 for rc in exited):
                # first failure kills the job (fleet-controller semantics)
                _terminate_rest(procs, rcs, grace_s)
                break
            if time.monotonic() > deadline:
                _terminate_rest(procs, rcs, grace_s=0.0)
                raise TimeoutError(
                    f"spawn_local: cluster did not finish in {timeout_s}s "
                    f"(exit codes so far: {rcs})")
            time.sleep(0.05)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return [p.wait() for p in procs]


def _terminate_rest(procs: List[subprocess.Popen],
                    rcs: List[Optional[int]], grace_s: float) -> None:
    live = [p for p in procs if p.poll() is None]
    if not live:
        return
    end = time.monotonic() + grace_s
    while time.monotonic() < end and any(p.poll() is None for p in live):
        time.sleep(0.05)
    for p in live:
        if p.poll() is None:
            p.terminate()
    for p in live:
        try:
            p.wait(timeout=5)
        except subprocess.TimeoutExpired:
            p.kill()


def main(argv: Optional[Sequence[str]] = None) -> int:
    """CLI: ``python -m paddle_tpu.distributed.bootstrap -n 2 script.py
    [args...]`` (tools/mp_launch.py is the thin wrapper)."""
    import argparse

    parser = argparse.ArgumentParser(
        prog="mp_launch",
        description="launch an emulated multi-process CPU jax cluster")
    parser.add_argument("-n", "--num-processes", type=int, default=2)
    parser.add_argument("-d", "--devices-per-process", type=int, default=1)
    parser.add_argument("--timeout", type=float, default=600.0)
    parser.add_argument("script")
    parser.add_argument("script_args", nargs=argparse.REMAINDER)
    args = parser.parse_args(argv)
    rcs = spawn_local(
        args.num_processes,
        [sys.executable, args.script, *args.script_args],
        devices_per_process=args.devices_per_process,
        timeout_s=args.timeout)
    print(f"mp_launch: exit codes {rcs}")
    return 0 if all(rc == 0 for rc in rcs) else 1


if __name__ == "__main__":
    sys.exit(main())
