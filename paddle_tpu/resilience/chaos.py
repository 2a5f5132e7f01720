"""Deterministic fault injection for resilience testing.

The reference Paddle proves its elastic story by SIGKILLing real trainer
processes (SURVEY.md §4); that is faithful but slow and non-deterministic.
Here faults are a seeded :class:`FaultPlan` — a *schedule* of injections
(NaN gradients at step S, a crash mid-checkpoint on save N, a truncated
or bit-flipped checkpoint file, a delayed or killed training step) that
instrumented code consults through module-level hooks.  The hooks are
no-ops unless a plan is ACTIVE (``with FaultPlan(...):``), so production
paths pay one ``is None`` check.

Determinism is the point: a chaos test that reproduces bit-identical
final weights across kill/resume (tests/test_resilience.py) is only
meaningful if the fault fires at exactly the same step with exactly the
same corruption every run.  All randomness (NaN positions, flipped bits)
derives from ``FaultPlan.seed``.

Instrumented sites:

- ``on_step(step)``        — training loop, once per batch (delay/kill)
- ``on_save(site)``        — checkpoint writers, mid-commit (crash)
- ``after_save(path)``     — checkpoint writers, post-commit (disk rot)
- ``maybe_fail_request(request_id)`` — serving prefill (poison request)
- ``maybe_fail_serving_step(label)`` — serving step watchdog (hung or
  failing compiled-step ATTEMPTS: delays register as watchdog stalls,
  exceptions exercise the bounded-retry path)
- ``maybe_fail_after_dispatch(label)`` — the same watchdog, once the
  attempt's call has returned: the step consumed its donated KV pool,
  so the fault cannot be retried (the lost-pool path)
- ``poison_batch(step, arrays)``     — data path (NaN/Inf gradients)

``burst_prompts`` is the matching ARRIVAL generator: a seeded batch of
random prompts for overload tests, so a shedding/degradation scenario
replays identically every run.
"""
from __future__ import annotations

import os
from typing import Dict, Iterable, List, Optional, Union

import numpy as np

__all__ = [
    "FaultPlan",
    "ChaosError",
    "SimulatedPreemption",
    "PROCESS_KILL_EXIT_CODE",
    "active_plan",
    "on_step",
    "on_save",
    "after_save",
    "maybe_fail_request",
    "maybe_fail_serving_step",
    "maybe_fail_after_dispatch",
    "poison_batch",
    "burst_prompts",
    "truncate_file",
    "bitflip_file",
]


class ChaosError(RuntimeError):
    """An injected fault (crash-mid-save, poisoned request, ...)."""


class SimulatedPreemption(ChaosError):
    """An injected kill of the training process at a scheduled step —
    catch it where the real preemption (SIGTERM) would end the run."""


_ACTIVE: Optional["FaultPlan"] = None

#: exit code used by hard process kills (``kill_hard=True``) so launchers
#: and tests can tell an injected death from a genuine crash.
PROCESS_KILL_EXIT_CODE = 43


def active_plan() -> Optional["FaultPlan"]:
    return _ACTIVE


def _process_index() -> int:
    """This process's cluster index (0 when not in a cluster) — lazy so
    single-process chaos never pulls in the distributed stack."""
    try:
        from ..distributed import bootstrap

        return bootstrap.process_index()
    except Exception:
        return 0


class FaultPlan:
    """A seeded, deterministic schedule of fault injections.

    Use as a context manager; entering activates the plan for every
    instrumented site in the process (one plan at a time — nesting
    raises, because two overlapping schedules cannot be deterministic).

    Parameters
    ----------
    seed: drives NaN positions and bit-flip offsets.
    nan_batch_steps: global steps whose batch is poisoned with NaN
        (``poison_batch``; float arrays only).
    inf_batch_steps: same, with +inf (a different non-finite pathology).
    kill_at_step: raise :class:`SimulatedPreemption` at this step's
        ``on_step`` — the in-process stand-in for SIGKILL.
    sigterm_at_step: deliver a REAL ``SIGTERM`` to this process at the
        step — exercises the checkpointer's preemption handler.
    delay_steps: {step: seconds} — sleep before the step runs.
    crash_on_save: 1-based ordinal of the ``on_save`` call that raises
        :class:`ChaosError` mid-commit (before the manifest/rename).
    corrupt_after_save: {1-based save ordinal: "truncate" | "bitflip"}
        — silently damage one committed checkpoint file on disk, the
        bit-rot / torn-write case integrity checking must catch.
    fail_request_ids: serving request ids whose prefill raises
        :class:`ChaosError` (the poison-request case).
    step_delay_s: injected latency into serving compiled-step ATTEMPTS
        (``maybe_fail_serving_step``, 1-based attempt ordinal counted
        across prefill+decode, retries included).  Either a plain float
        — every attempt sleeps that long, the sustained-slowdown case —
        or ``{ordinal: seconds}`` for targeted hangs.  The sleep lands
        inside the engine watchdog's timed window, so a big enough
        delay IS a detected stall.
    fail_step_at: 1-based serving-step attempt ordinals that raise
        :class:`ChaosError` instead of running — the transient device
        failure the watchdog's bounded retry must absorb (consecutive
        ordinals exhaust the retries and quarantine the engine).
    fail_after_dispatch_at: serving-step attempt ordinals (the same
        count as ``fail_step_at``) that raise :class:`ChaosError` AFTER
        the attempt's call returned, still inside the watchdog's
        window: the program has consumed its donated KV pool, so no
        retry can run and the engine quarantines at once.
    kill_process_at: ``{step: process_index}`` — process-scoped kill:
        at ``on_step(step)``, ONLY the process whose cluster index
        (``distributed.bootstrap.process_index()``) matches dies; its
        peers keep running until the fleet supervisor notices.  With
        ``kill_hard=False`` (default) the death is a raised
        :class:`SimulatedPreemption`; with ``kill_hard=True`` it is
        ``os._exit`` — no cleanup, no atexit, the honest SIGKILL stand-in
        for multi-process crash tests.
    kill_save_site: substring matched against checkpoint ``on_save``
        sites; the first in-scope matching call (see
        ``kill_save_site_ordinal``) dies mid-save.  The sharded
        checkpointer's sites make every protocol window targetable:
        ``"resilience::shard:"`` (mid-shard-write, torn shard file),
        ``"resilience::shards_done"`` (between shards and manifest),
        ``"resilience::manifest"`` (before the manifest lands),
        ``"resilience::commit"`` (manifest written, rename pending).
    save_fault_process: scope ``kill_save_site`` to one cluster process
        index (``None`` = any process).
    kill_save_site_ordinal: 1-based ordinal among in-scope matching
        ``on_save`` calls that actually dies (default: the first).
    kill_hard: make ``kill_process_at`` / ``kill_save_site`` deaths
        ``os._exit(PROCESS_KILL_EXIT_CODE)`` instead of raised
        exceptions.
    step_fault_scope: when set, ONLY serving-step attempts whose label
        contains this substring are counted and faulted — the others
        pass through untouched (their ordinals do not advance the
        schedule).  A fleet of named replicas labels its steps
        ``serving::decode_step@<name>`` (ServingConfig(name=...)), so
        ``step_fault_scope="@replica-1"`` kills or stalls exactly one
        replica of a router while its siblings keep serving —
        deterministic replica-targeted chaos.
    """

    def __init__(self, seed: int = 0,
                 nan_batch_steps: Iterable[int] = (),
                 inf_batch_steps: Iterable[int] = (),
                 kill_at_step: Optional[int] = None,
                 sigterm_at_step: Optional[int] = None,
                 delay_steps: Optional[Dict[int, float]] = None,
                 crash_on_save: Optional[int] = None,
                 corrupt_after_save: Optional[Dict[int, str]] = None,
                 fail_request_ids: Iterable[str] = (),
                 step_delay_s: Union[None, float,
                                     Dict[int, float]] = None,
                 fail_step_at: Iterable[int] = (),
                 fail_after_dispatch_at: Iterable[int] = (),
                 step_fault_scope: Optional[str] = None,
                 kill_process_at: Optional[Dict[int, int]] = None,
                 kill_save_site: Optional[str] = None,
                 save_fault_process: Optional[int] = None,
                 kill_save_site_ordinal: int = 1,
                 kill_hard: bool = False):
        self.seed = seed
        self.nan_batch_steps = frozenset(nan_batch_steps)
        self.inf_batch_steps = frozenset(inf_batch_steps)
        self.kill_at_step = kill_at_step
        self.sigterm_at_step = sigterm_at_step
        self.delay_steps = dict(delay_steps or {})
        self.crash_on_save = crash_on_save
        self.corrupt_after_save = dict(corrupt_after_save or {})
        for kind in self.corrupt_after_save.values():
            if kind not in ("truncate", "bitflip"):
                raise ValueError(f"unknown corruption kind {kind!r}")
        self.fail_request_ids = frozenset(fail_request_ids)
        self.step_delay_s = step_delay_s
        self.fail_step_at = frozenset(fail_step_at)
        self.fail_after_dispatch_at = frozenset(fail_after_dispatch_at)
        self.step_fault_scope = step_fault_scope
        self.kill_process_at = dict(kill_process_at or {})
        self.kill_save_site = kill_save_site
        self.save_fault_process = save_fault_process
        self.kill_save_site_ordinal = kill_save_site_ordinal
        self.kill_hard = kill_hard
        # observability: what actually fired (tests assert on these)
        self.injected: list = []
        self._save_calls = 0
        self._save_site_hits = 0
        self._serving_step_calls = 0

    # ------------------------------------------------------------ scope
    def __enter__(self) -> "FaultPlan":
        global _ACTIVE
        if _ACTIVE is not None:
            raise RuntimeError("a FaultPlan is already active; chaos "
                               "schedules do not nest")
        _ACTIVE = self
        return self

    def __exit__(self, *exc):
        global _ACTIVE
        _ACTIVE = None
        return False

    # ------------------------------------------------------------ hooks
    def on_step(self, step: int):
        delay = self.delay_steps.get(step)
        if delay:
            import time

            self.injected.append(("delay", step))
            time.sleep(delay)
        if self.sigterm_at_step == step:
            import signal

            self.injected.append(("sigterm", step))
            os.kill(os.getpid(), signal.SIGTERM)
        if self.kill_at_step == step:
            self.injected.append(("kill", step))
            raise SimulatedPreemption(f"injected kill at step {step}")
        victim = self.kill_process_at.get(step)
        if victim is not None and victim == _process_index():
            self.injected.append(("kill_process", step, victim))
            self._die(f"injected process kill: step {step} "
                      f"process {victim}")

    def _die(self, reason: str):
        """A process-scoped death: hard (``os._exit``, the SIGKILL
        stand-in — no cleanup, no flushed buffers) or soft (raised
        :class:`SimulatedPreemption`)."""
        if self.kill_hard:
            import sys as _sys

            print(f"[chaos] {reason} (os._exit)", file=_sys.stderr,
                  flush=True)
            os._exit(PROCESS_KILL_EXIT_CODE)
        raise SimulatedPreemption(reason)

    def on_save(self, site: str):
        self._save_calls += 1
        if self.crash_on_save == self._save_calls:
            self.injected.append(("crash_save", site))
            raise ChaosError(
                f"injected crash during checkpoint save #{self._save_calls} "
                f"({site})")
        if self.kill_save_site is not None and self.kill_save_site in site:
            if self.save_fault_process is None \
                    or self.save_fault_process == _process_index():
                self._save_site_hits += 1
                if self._save_site_hits == self.kill_save_site_ordinal:
                    self.injected.append(("kill_save", site))
                    self._die(f"injected death mid-save at {site}")

    def after_save(self, path: str):
        kind = self.corrupt_after_save.get(self._save_calls)
        if kind is None:
            return
        victim = _largest_payload_file(path)
        if victim is None:
            return
        if kind == "truncate":
            truncate_file(victim)
        else:
            bitflip_file(victim, seed=self.seed)
        self.injected.append((kind, victim))

    def maybe_fail_request(self, request_id: str):
        if request_id in self.fail_request_ids:
            self.injected.append(("fail_request", request_id))
            raise ChaosError(f"injected prefill failure for {request_id}")

    def maybe_fail_serving_step(self, label: str):
        """One serving compiled-step ATTEMPT (prefill chunk or decode
        iteration, retries counted separately) — sleep and/or raise per
        the schedule.  Called inside the engine watchdog's monotonic
        window, so injected delays are observed as stalls.  With a
        ``step_fault_scope``, attempts outside the scope pass through
        without advancing the schedule (replica-targeted chaos)."""
        if self.step_fault_scope is not None \
                and self.step_fault_scope not in label:
            return
        self._serving_step_calls += 1
        n = self._serving_step_calls
        delay = (self.step_delay_s if isinstance(
            self.step_delay_s, (int, float))
            else (self.step_delay_s or {}).get(n))
        if delay:
            import time

            self.injected.append(("serving_delay", n, label))
            time.sleep(delay)
        if n in self.fail_step_at:
            self.injected.append(("serving_fail", n, label))
            raise ChaosError(
                f"injected serving step failure at attempt {n} ({label})")

    def maybe_fail_after_dispatch(self, label: str):
        """The attempt that ``maybe_fail_serving_step`` last counted has
        returned from its call: raise if its ordinal is scheduled."""
        if self.step_fault_scope is not None \
                and self.step_fault_scope not in label:
            return
        n = self._serving_step_calls
        if n in self.fail_after_dispatch_at:
            self.injected.append(("serving_fail_after_dispatch", n, label))
            raise ChaosError(
                f"injected failure after dispatch of attempt {n} ({label})")

    def poison_batch(self, step: int, arrays):
        """Return ``arrays`` (a list/tuple of numpy arrays) with NaN/Inf
        written into the float entries when ``step`` is scheduled;
        positions are seeded, so reruns poison identically."""
        bad = (np.nan if step in self.nan_batch_steps
               else np.inf if step in self.inf_batch_steps else None)
        if bad is None:
            return arrays
        rng = np.random.RandomState(self.seed * 100003 + step)
        out = []
        poisoned = False
        for a in arrays:
            a = np.asarray(a)
            if np.issubdtype(a.dtype, np.floating) and a.size:
                a = a.copy()
                flat = a.reshape(-1)
                k = max(1, flat.size // 8)
                flat[rng.choice(flat.size, size=k, replace=False)] = bad
                poisoned = True
            out.append(a)
        if poisoned:
            self.injected.append(("poison", step))
        return out


# ---------------------------------------------------------------------------
# module-level hooks (what instrumented code actually calls)
# ---------------------------------------------------------------------------

def on_step(step: int):
    if _ACTIVE is not None:
        _ACTIVE.on_step(step)


def on_save(site: str):
    if _ACTIVE is not None:
        _ACTIVE.on_save(site)


def after_save(path: str):
    if _ACTIVE is not None:
        _ACTIVE.after_save(path)


def maybe_fail_request(request_id: str):
    if _ACTIVE is not None:
        _ACTIVE.maybe_fail_request(request_id)


def maybe_fail_serving_step(label: str):
    if _ACTIVE is not None:
        _ACTIVE.maybe_fail_serving_step(label)


def maybe_fail_after_dispatch(label: str):
    if _ACTIVE is not None:
        _ACTIVE.maybe_fail_after_dispatch(label)


def burst_prompts(seed: int, n: int, min_len: int = 4,
                  max_len: int = 32, vocab: int = 256
                  ) -> List[np.ndarray]:
    """Seeded burst-arrival generator: ``n`` random int32 prompts with
    lengths uniform in ``[min_len, max_len]`` — the deterministic
    traffic spike overload tests and the overload bench replay so
    shedding-on and shedding-off see the IDENTICAL workload."""
    rng = np.random.RandomState(seed)
    return [rng.randint(1, vocab,
                        size=(int(rng.randint(min_len, max_len + 1)),)
                        ).astype(np.int32)
            for _ in range(n)]


def poison_batch(step: int, arrays):
    if _ACTIVE is None:
        return arrays
    return _ACTIVE.poison_batch(step, arrays)


# ---------------------------------------------------------------------------
# disk corruption utilities (also usable directly from tests)
# ---------------------------------------------------------------------------

def _largest_payload_file(path: str) -> Optional[str]:
    """The biggest non-manifest file under ``path`` (or ``path`` itself
    when it is a file) — the state payload a torn write would hit."""
    if os.path.isfile(path):
        return path
    best, best_size = None, -1
    for root, _dirs, files in os.walk(path):
        for f in files:
            if f == "manifest.json":
                continue
            p = os.path.join(root, f)
            size = os.path.getsize(p)
            if size > best_size:
                best, best_size = p, size
    return best


def truncate_file(path: str, keep_frac: float = 0.5):
    """Truncate ``path`` to ``keep_frac`` of its size (a torn write)."""
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(max(0, int(size * keep_frac)))


def bitflip_file(path: str, nbits: int = 8, seed: int = 0):
    """Flip ``nbits`` seeded-random bits in ``path`` (silent bit rot)."""
    size = os.path.getsize(path)
    if size == 0:
        return
    rng = np.random.RandomState(seed)
    with open(path, "r+b") as f:
        for _ in range(nbits):
            off = int(rng.randint(0, size))
            f.seek(off)
            byte = f.read(1)
            f.seek(off)
            f.write(bytes([byte[0] ^ (1 << int(rng.randint(0, 8)))]))
