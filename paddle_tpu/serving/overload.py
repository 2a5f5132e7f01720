# lint-tpu: disable-file=L004 -- serving-layer host-side control plane
# (like engine.py); new backend code belongs under core/ ops/ kernels/
"""Overload control for the serving engine: load shedding, a KV
memory-pressure degradation ladder, and a hung-step watchdog
(PAPERS.md: Sarathi/vLLM-tradition graceful degradation — README
"Overload control & graceful degradation").

Three cooperating mechanisms, all host-side (nothing here touches a
traced program, so the H106 no-host-work and no-retrace contracts are
untouched):

* **Load shedding** (:class:`AdmissionController`): at ``submit()``
  time, estimate the candidate's TTFT from the queue depth, the pending
  prefill tokens ahead of it, and EWMAs of the compiled chunk/decode
  step latencies.  When the OPTIMISTIC estimate already busts
  ``deadline_s``, retire the request immediately with
  ``finish_reason="shed"`` — a cheap rejection at admission beats a
  guaranteed timeout after burning prefill compute.  Sheds never fire
  while the EWMAs are cold (a fresh engine admits everything).

* **Degradation ladder** (:class:`DegradationLadder`): high/low
  watermarks with hysteresis over the pool's used fraction
  (free + parked blocks both count as headroom, matching
  ``BlockKVPool.num_free``).  Strictly above the high watermark the
  engine walks one level per iteration: evict parked prefix-cache blocks → shrink
  the effective prefill token budget to one chunk per iteration → pause
  admissions → preempt the youngest/lowest-priority running request.
  Below the low watermark it unwinds one level per iteration.  Every
  transition is a gauge (``serving_degradation_level``) and a log line.

* **Step watchdog** (:class:`StepWatchdog`): wraps each host-side call
  into the compiled prefill/decode steps with a monotonic-clock budget
  (``watchdog_budget_mult`` × the step's EWMA latency, floored by
  ``watchdog_floor_s`` so the first-call compile never trips it).  The
  step programs DONATE the KV pool (models/generation.py), so a call
  that was dispatched has consumed its operands, and what the watchdog
  may do follows from that.  A *stall* (the attempt returned, late) is
  counted, marks the engine ``DEGRADED`` and KEEPS its result: the step
  completed and advanced the pool, nothing is dispatched again.  An
  exception raised while the operands are still live (before the
  program took them) gets bounded retries with exponential backoff,
  then ``FAILED`` (:class:`EngineQuarantined` propagates out of
  ``step()``).  An exception after the operands were consumed leaves
  nothing to retry on: ``FAILED`` at once, counter ``pool_lost``.
  ``DEGRADED`` self-heals after ``health_recovery_steps`` consecutive
  in-budget steps; ``FAILED`` needs an explicit ``Engine.revive()``,
  which also gives a lost pool fresh buffers and sends the stranded
  requests back through recompute.
"""
from __future__ import annotations

import logging
import math
import time
from typing import Callable, List, Optional, Tuple

from .cache import consumed

log = logging.getLogger("paddle_tpu.serving")

# engine health states (Engine.health()["state"])
SERVING = "serving"
DEGRADED = "degraded"
FAILED = "failed"

_HEALTH_CODE = {SERVING: 0, DEGRADED: 1, FAILED: 2}

# degradation-ladder levels, walked one step per engine iteration
LADDER_LEVELS = ("normal", "evict_cache", "shrink_prefill",
                 "pause_admissions", "preempt")


class EngineQuarantined(RuntimeError):
    """The step watchdog exhausted its bounded retries on step
    exceptions, or a step failed after it had consumed the pool: the
    engine is quarantined FAILED and refuses work until
    ``Engine.revive()``."""


class LatencyEWMA:
    """Exponentially-weighted moving average of a step latency.

    The FIRST observation is recorded separately as ``compile_s`` and
    kept out of the average — it is dominated by XLA compilation and
    would otherwise poison both the TTFT estimate (over-shedding) and
    the watchdog budget for the engine's whole lifetime."""

    def __init__(self, alpha: float = 0.2):
        self.alpha = alpha
        self.value: Optional[float] = None
        self.compile_s: Optional[float] = None
        self.samples = 0

    def observe(self, dt: float):
        if self.compile_s is None:
            self.compile_s = dt
            return
        self.samples += 1
        self.value = dt if self.value is None else (
            self.alpha * dt + (1.0 - self.alpha) * self.value)

    @property
    def warmed(self) -> bool:
        return self.value is not None


class EngineHealth:
    """SERVING / DEGRADED / FAILED state machine fed by the watchdogs.

    DEGRADED (stalls detected, engine still producing) self-heals after
    ``recovery_steps`` consecutive in-budget steps; FAILED (retries
    exhausted on step exceptions, or the pool lost) is sticky until
    ``revive()``."""

    def __init__(self, metrics=None, recovery_steps: int = 3):
        self.state = SERVING
        self.recovery_steps = recovery_steps
        self.last_error: Optional[str] = None
        self._clean = 0
        self._metrics = metrics
        self._publish()

    def _publish(self):
        if self._metrics is not None:
            self._metrics.on_health(_HEALTH_CODE[self.state])

    def _transition(self, new: str, why: str):
        if new != self.state:
            log.warning("engine health %s -> %s (%s)",
                        self.state, new, why)
            self.state = new
            self._publish()

    def on_stall(self, label: str, dt: float, budget: float):
        self._clean = 0
        if self.state != FAILED:
            self._transition(
                DEGRADED, f"{label} stalled {dt:.3f}s > {budget:.3f}s")

    def on_failure(self, label: str, error: BaseException):
        self.last_error = f"{type(error).__name__}: {error}"
        self._clean = 0
        self._transition(FAILED, f"{label}: {self.last_error}")

    def on_clean_step(self):
        if self.state == DEGRADED:
            self._clean += 1
            if self._clean >= self.recovery_steps:
                self._transition(
                    SERVING, f"{self._clean} consecutive in-budget steps")
        else:
            self._clean = 0

    def revive(self):
        """Operator override: clear FAILED/DEGRADED back to SERVING."""
        self.last_error = None
        self._clean = 0
        self._transition(SERVING, "revive()")

    @property
    def failed(self) -> bool:
        return self.state == FAILED


class StepWatchdog:
    """Monotonic-clock watchdog + bounded retry around ONE compiled
    step entry point (decode or chunked prefill).

    Timing wraps the host-side dispatch only — no synchronization is
    added inside a traced program, so registered step jaxprs stay
    H106-clean.  The chaos serving-step hooks fire INSIDE the timed
    window: one before the device call (injected delays register as
    stalls, injected exceptions exercise the retry path, operands
    live), one after it returned (a fault once the pool is consumed)."""

    def __init__(self, label: str, ewma: LatencyEWMA, health: EngineHealth,
                 metrics, *, budget_mult: float, floor_s: float,
                 max_retries: int, backoff_s: float):
        self.label = label
        self.ewma = ewma
        self.health = health
        self.metrics = metrics
        self.budget_mult = budget_mult
        self.floor_s = floor_s
        self.max_retries = max_retries
        self.backoff_s = backoff_s
        self.stalls = 0
        self.retries = 0

    def budget_s(self) -> float:
        """Per-attempt latency budget: a multiple of the EWMA, floored
        generously so the first-call XLA compile never trips it."""
        if not self.ewma.warmed:
            return self.floor_s
        return max(self.floor_s, self.budget_mult * self.ewma.value)

    def call(self, fn: Callable, *args):
        """Run ``fn(*args)`` under the budget.

        Stall (slow but successful) → count it, mark the engine
        DEGRADED and keep the result: the step completed and its
        donated pool is gone, so running it again could only buy the
        same result later.  Exception with the operands live → retry
        with exponential backoff (the same program on the same
        operands: jit-cache hit, zero retraces); exhausted → quarantine
        FAILED and raise :class:`EngineQuarantined`.  Exception with the
        operands consumed → no retry can run: ``pool_lost``, FAILED at
        once."""
        from ..observability import RetraceError
        from ..resilience import chaos

        last_error: Optional[BaseException] = None
        for attempt in range(self.max_retries + 1):
            if attempt:
                time.sleep(self.backoff_s * (2 ** (attempt - 1)))
            t0 = time.monotonic()
            try:
                chaos.maybe_fail_serving_step(self.label)
                out = fn(*args)
                chaos.maybe_fail_after_dispatch(self.label)
            except RetraceError:
                raise       # contract violation, not a transient fault
            except Exception as e:  # noqa: BLE001 — bounded retry
                if consumed(args):
                    self.metrics.on_pool_lost(self.label)
                    self.health.on_failure(self.label, e)
                    raise EngineQuarantined(
                        f"{self.label}: failed after the program took "
                        f"its pool ({e!r}); engine quarantined FAILED, "
                        "revive() rebuilds the pool") from e
                last_error = e
                self.retries += 1
                self.metrics.on_step_retry(self.label)
                log.warning("%s attempt %d/%d failed: %s", self.label,
                            attempt + 1, self.max_retries + 1, e)
                continue
            dt = time.monotonic() - t0
            budget = self.budget_s()
            if dt > budget:
                self.stalls += 1
                self.metrics.on_watchdog_stall(self.label)
                self.health.on_stall(self.label, dt, budget)
                return out      # late, but done: degrade, keep it
            self.ewma.observe(dt)
            self.health.on_clean_step()
            return out
        self.health.on_failure(self.label, last_error)
        raise EngineQuarantined(
            f"{self.label}: {self.max_retries + 1} attempts failed; "
            f"engine quarantined FAILED (last: {last_error!r})"
        ) from last_error


class DegradationLadder:
    """Hysteresis watermarks over KV-pool pressure driving the explicit
    degradation ladder (module docstring).  One level per engine
    iteration in either direction — escalation is deliberate, and the
    unwind retraces the same rungs."""

    def __init__(self, metrics, *, high: float, low: float):
        if not 0.0 <= low <= high <= 1.0:
            raise ValueError(
                f"watermarks need 0 <= low <= high <= 1, got "
                f"low={low} high={high}")
        self.high = high
        self.low = low
        self.level = 0
        self.metrics = metrics
        # (engine iteration ordinal, new level) — tests assert ordering
        self.transitions: List[Tuple[int, int]] = []
        self._ticks = 0

    @property
    def level_name(self) -> str:
        return LADDER_LEVELS[self.level]

    @property
    def admissions_paused(self) -> bool:
        return self.level >= LADDER_LEVELS.index("pause_admissions")

    def effective_prefill_budget(self, configured: int) -> int:
        """Shrink the per-iteration prefill token budget to ONE token
        at or above the shrink level — each chunk still advances a full
        ``chunk_tokens`` (fixed compiled shape), but only one chunk runs
        per iteration, keeping decode responsive under pressure."""
        if self.level >= LADDER_LEVELS.index("shrink_prefill"):
            return 1
        return configured

    def _set_level(self, level: int, pressure: float):
        log.warning(
            "degradation ladder %s -> %s (kv pressure %.2f, "
            "high=%.2f low=%.2f)", self.level_name,
            LADDER_LEVELS[level], pressure, self.high, self.low)
        self.level = level
        self.transitions.append((self._ticks, level))
        self.metrics.on_degradation_level(level)

    def tick(self, engine) -> int:
        """One hysteresis step against current pool pressure, applying
        the newly-reached level's action.  Returns the level."""
        self._ticks += 1
        # BYTE-denominated pressure: used KV bytes over the pool's byte
        # capacity (scale sidecars included), so the watermark is a
        # statement about HBM, not block counts.  Two engines sized
        # from the same kv_pool_bytes budget at different KV dtypes see
        # comparable pressure per resident byte — the quantized one
        # fits ~4x the blocks, so the SAME burst crosses the high
        # watermark later at int8 than at fp32 (dtype-aware ladder,
        # ISSUE 20).
        pressure = engine.pool.byte_utilization()
        # STRICTLY above the high watermark: the default high=1.0 can
        # never be exceeded (a fully-referenced pool is the engine's
        # normal preemption-managed regime, and tiny test pools live
        # there), so the ladder engages only when a deployment sets
        # kv_high_watermark < 1.0
        if pressure > self.high and self.level < len(LADDER_LEVELS) - 1:
            self._set_level(self.level + 1, pressure)
        elif pressure < self.low and self.level > 0:
            self._set_level(self.level - 1, pressure)
        if self.level >= LADDER_LEVELS.index("evict_cache"):
            # parked prefix blocks are reclaimable headroom; under
            # pressure give them back eagerly instead of lazily via
            # allocate()'s LRU fallback
            engine.pool.evict_parked()
        if self.level >= LADDER_LEVELS.index("preempt") \
                and len(engine.scheduler.running) > 1:
            # shed running work, lowest-priority/youngest first; never
            # the sole running request (preempting it frees nothing
            # durable — it would bounce straight back)
            victim = engine.scheduler.pick_victim()
            if victim is not None:
                engine._preempt(victim)
        return self.level


class OverloadController:
    """Facade owned by the engine bundling the EWMAs, admission
    controller, ladder, health state, and the two step watchdogs."""

    def __init__(self, config, metrics):
        self.config = config
        self.metrics = metrics
        self.chunk_ewma = LatencyEWMA()
        self.decode_ewma = LatencyEWMA()
        self.health = EngineHealth(
            metrics, recovery_steps=config.health_recovery_steps)
        self.ladder = DegradationLadder(
            metrics, high=config.kv_high_watermark,
            low=config.kv_low_watermark)
        # a named replica (ServingConfig(name=...), fleet routing) tags
        # its step labels so chaos plans and metrics can target ONE
        # engine; the default stays the bare single-engine label
        tag = f"@{config.name}" if getattr(config, "name", "") else ""
        self.prefill_watchdog = StepWatchdog(
            f"serving::prefill_step{tag}", self.chunk_ewma, self.health,
            metrics, budget_mult=config.watchdog_budget_mult,
            floor_s=config.watchdog_floor_s,
            max_retries=config.step_max_retries,
            backoff_s=config.step_retry_backoff_s)
        self.decode_watchdog = StepWatchdog(
            f"serving::decode_step{tag}", self.decode_ewma, self.health,
            metrics, budget_mult=config.watchdog_budget_mult,
            floor_s=config.watchdog_floor_s,
            max_retries=config.step_max_retries,
            backoff_s=config.step_retry_backoff_s)
        self._tag = tag

    def extra_watchdog(self, kind: str) -> StepWatchdog:
        """A watchdog for an ADDITIONAL compiled step entry point (the
        speculative draft/verify steps) with its OWN LatencyEWMA.
        Sharing one EWMA across two programs would record the second
        program's first-call compile as a real latency sample — the
        exact poisoning the per-EWMA ``compile_s`` carve-out exists to
        prevent — inflating the watchdog budget and the TTFT estimate
        (over-shedding) for the engine's whole lifetime."""
        return StepWatchdog(
            f"serving::{kind}{self._tag}", LatencyEWMA(), self.health,
            self.metrics, budget_mult=self.config.watchdog_budget_mult,
            floor_s=self.config.watchdog_floor_s,
            max_retries=self.config.step_max_retries,
            backoff_s=self.config.step_retry_backoff_s)

    # ------------------------------------------------------ load shedding
    def can_estimate(self) -> bool:
        """Shedding only fires once the chunk EWMA has a real (post-
        compile) sample: a fresh engine has no basis for an estimate and
        must admit everything (cold-start safety)."""
        return self.config.enable_load_shedding and self.chunk_ewma.warmed

    def estimate_ttft_s(self, engine, prompt) -> float:
        """Optimistic TTFT estimate for a CANDIDATE prompt arriving now:
        every prefill token ahead of it (waiting queue + mid-prefill
        remainders) plus its own uncached tokens, paced by the per-
        iteration prefill budget with one decode step interleaved per
        iteration.  Optimistic by design — it ignores decode-slot
        contention and future arrivals — so a shed only happens when
        even the best case busts the deadline."""
        C = engine.chunk_tokens
        chunk_s = self.chunk_ewma.value
        decode_s = self.decode_ewma.value or 0.0
        pending = engine.pending_prefill_tokens()
        matched, _, _ = engine.pool.admission_plan(prompt, extra_tokens=0)
        own = max(1, len(prompt) - len(matched) * engine.pool.block_size)
        chunks = math.ceil(pending / C) + math.ceil(own / C)
        budget = self.ladder.effective_prefill_budget(
            self.config.prefill_token_budget or C)
        chunks_per_iter = max(1, budget // C)
        iters = math.ceil(chunks / chunks_per_iter)
        return chunks * chunk_s + iters * decode_s

    def should_shed(self, engine, prompt,
                    deadline_s: Optional[float]) -> bool:
        if deadline_s is None or not self.can_estimate():
            return False
        est = self.estimate_ttft_s(engine, prompt)
        shed = est > deadline_s * self.config.shed_safety_factor
        if shed:
            log.info("shedding request: est TTFT %.3fs > deadline %.3fs",
                     est, deadline_s)
        return shed

    # ------------------------------------------------------------- health
    def snapshot(self, engine) -> dict:
        """``Engine.health()`` payload — a host-side dict, cheap enough
        for a load balancer to poll every second."""
        return {
            "state": self.health.state,
            "last_error": self.health.last_error,
            "degradation_level": self.ladder.level,
            "degradation_level_name": self.ladder.level_name,
            "admissions_paused": self.ladder.admissions_paused,
            "watchdog_stalls": (self.prefill_watchdog.stalls
                                + self.decode_watchdog.stalls),
            "step_retries": (self.prefill_watchdog.retries
                             + self.decode_watchdog.retries),
            "ewma_chunk_s": self.chunk_ewma.value,
            "ewma_decode_s": self.decode_ewma.value,
            "queue_depth": len(engine.scheduler.waiting),
            "kv_pressure": engine.pool.byte_utilization(),
            "kv_dtype": engine.pool.kv_dtype_tag,
            "kv_used_bytes": engine.pool.used_bytes(),
            "kv_capacity_bytes": engine.pool.capacity_bytes(),
        }


__all__ = ["SERVING", "DEGRADED", "FAILED", "LADDER_LEVELS",
           "EngineQuarantined", "LatencyEWMA", "EngineHealth",
           "StepWatchdog", "DegradationLadder", "OverloadController"]
