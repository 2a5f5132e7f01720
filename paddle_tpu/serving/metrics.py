"""Request-level observability for the serving engine.

Per-request timings (TTFT, TPOT, queue time, tokens generated) plus
engine-level counters and gauges (batch occupancy, cache utilization,
preemptions), exportable three ways:

- ``as_dict()`` — everything, JSON-ready (the metrics schema in
  README "Serving");
- ``step()`` and ``phase(name)`` — the span ``serving::step`` around
  one ``Engine.step()`` and the eight inside it (``serving::admit`` …
  ``serving::pool_sync``, flat among themselves): each is a
  ``profiler.RecordEvent``, so it lands in any ``jax.profiler`` trace
  on the device trace's clock (and in an active ``paddle_tpu.profiler``
  session's chrome export), and its host time accumulates into the
  ``step_ns.<phase>`` / ``step_wall_ns`` counters whether or not
  anything records.  Every few steps, and after a slow one, the
  calling thread's CPU time is read too (``step_cpu_ns``), so that a
  step that stood still is told from one that ran; a step far over the
  usual one (``SLOW_STEP_FACTOR``)
  leaves one record of what the host was doing in it
  (``as_dict()["slow_steps"]``, the last ``SLOW_STEP_LOG`` of them);
- the shared ``paddle_tpu.observability`` registry — every lifecycle
  event is mirrored (``serving_*`` counters/gauges, TTFT/TPOT/queue/e2e
  latency histograms) whenever telemetry is enabled, so serving shows
  up in the same Prometheus/JSON exports as training and resilience.

The ``as_dict()`` schema is a contract (README "Serving") and is
unchanged by the registry mirror.
"""
from __future__ import annotations

import functools
import gc
import time
from collections import deque
from dataclasses import dataclass
from types import SimpleNamespace
from typing import Dict, Optional

from ..observability import registry as _obsreg
from ..profiler import RecordEvent

_now_ns = time.perf_counter_ns
_cpu_ns = time.thread_time_ns     # of the thread that calls Engine.step()

# the calling thread's context switches (``ru_nvcsw`` voluntary,
# ``ru_nivcsw`` involuntary) and page faults (``ru_minflt``,
# ``ru_majflt``) so far; zeros where the platform keeps none by thread
# (no ``RUSAGE_THREAD``; or, as the sandboxed kernel of the benchmark's
# hosts, every count 0 in a thread that has imported all of this, at the
# price of a system call of 6 us each time it is asked)
_NO_USAGE = SimpleNamespace(ru_nvcsw=0, ru_nivcsw=0, ru_minflt=0,
                            ru_majflt=0)
try:
    import resource
    _thread_usage = functools.partial(resource.getrusage,
                                      resource.RUSAGE_THREAD)
    if not _thread_usage().ru_minflt:
        raise OSError("RUSAGE_THREAD counts nothing")
except (ImportError, AttributeError, OSError):
    def _thread_usage():
        return _NO_USAGE

# the phases of one ``Engine.step()``, in the order they run; a span is
# named ``serving::<phase>``
PHASES = ("admit", "prefill_dispatch", "first_token", "decode_prepare",
          "decode_dispatch", "decode_fetch", "sample_emit", "pool_sync")
_SPAN_NAMES = {phase: "serving::" + phase for phase in PHASES}

# A step is slow when its wall time is over this many times the usual
# step's (``ServingMetrics._usual_ns``: a mean in which a step weighs
# 1/64 at most).  The largest ordinary iteration of a cell is 2.3 times
# its usual one and the smallest stall on record 5.4 times (PERF.md
# sections 3 and 6, PR 38).
SLOW_STEP_FACTOR = 3
_USUAL_STEPS = 64       # steps the usual step is the mean of
SLOW_STEP_LOG = 64      # records of slow steps kept
# The calling thread's CPU clock and its usage are system calls (6 us in
# a tight loop and several times that between other work on the
# sandboxed kernel of the benchmark's hosts, where two of them at each
# end of every step were 0.1 ms a step): they are read at the end of a
# slow step and of every so-many-th other step, and a reading is
# compared with the one before it.
THREAD_READ_EVERY = 8
FINISHED_REQUESTS = 1024    # timelines of retired requests kept


_collections = [0]      # passes of Python's collector in this process


def _on_collection(phase, info):
    if phase == "stop":
        _collections[0] += 1


# counted as they happen: ``gc.get_stats()`` at both ends of every step
# would build six dicts a step to learn the same
gc.callbacks.append(_on_collection)


class _Phase:
    """One open span of ``ServingMetrics.phase``."""

    __slots__ = ("_metrics", "_phase", "_event", "_t0")

    def __init__(self, metrics, phase, metadata):
        self._metrics = metrics
        self._phase = phase
        self._event = RecordEvent(_SPAN_NAMES[phase], **metadata)

    def __enter__(self):
        self._event.begin()
        self._t0 = _now_ns()
        return self

    def __exit__(self, *exc):
        spent = _now_ns() - self._t0
        self._event.end()
        self._metrics.step_ns[self._phase] += spent
        return False


class _Step:
    """The open span and account of one ``Engine.step()``
    (``ServingMetrics.step``)."""

    __slots__ = ("_metrics", "_event", "_t0", "_collections", "_chunks",
                 "_phase_ns")

    def __init__(self, metrics):
        self._metrics = metrics
        self._event = RecordEvent("serving::step",
                                  step=metrics.engine_steps)

    def __enter__(self):
        m = self._metrics
        self._t0 = _now_ns()
        self._event.begin()
        self._phase_ns = tuple(m.step_ns.values())
        self._chunks = m.prefill_chunks_run
        self._collections = _collections[0]
        m._step_slots = m._step_behind = 0
        return self

    def __exit__(self, exc_type, exc, tb):
        m = self._metrics
        self._event.end()
        end = _now_ns()
        wall = end - self._t0
        chunks = m.prefill_chunks_run - self._chunks
        slow = compiled = 0
        # a step that dispatched nothing had no one waiting for it: it
        # is neither slow nor part of the usual step
        if chunks or m._step_slots:
            usual = m._usual_ns
            # (the step programs run inside steps only)
            compiled = m.programs_compiled() - m._compiled_seen
            m._compiled_seen += compiled
            slow = m._usual_seen >= _USUAL_STEPS \
                and wall > SLOW_STEP_FACTOR * usual
            if not compiled:
                # a slow step enters the mean as SLOW_STEP_FACTOR usual
                # ones at most: a stall of seconds moves it by 3 %, and
                # a load that has changed for good is followed within a
                # dozen steps (left out of it, the mean would stay where
                # it was and every step after the change read as slow)
                m._usual_seen = seen = min(m._usual_seen + 1, _USUAL_STEPS)
                m._usual_ns = usual + (
                    (min(wall, SLOW_STEP_FACTOR * usual) if seen > 1
                     else wall) - usual) / seen
        m._steps_unread += 1
        if slow or m._steps_unread >= THREAD_READ_EVERY:
            thread = m._read_thread(end)
            if slow:
                m.slow_steps += 1
                m.slow_step_excess_ns += wall - int(usual)
                phase_ns = [b - a for a, b in zip(self._phase_ns,
                                                  m.step_ns.values())]
                m.slow_step_log.append({
                    "step": m.engine_steps, "start_ns": self._t0,
                    "wall_ns": wall, "usual_ns": int(usual),
                    "phase_ns": dict(zip(PHASES, phase_ns)),
                    "unowned_ns": wall - sum(phase_ns), "chunks": chunks,
                    "slots": m._step_slots,
                    "programs_behind": m._step_behind, **thread,
                    "gc_collections": _collections[0] - self._collections,
                    "programs_compiled": compiled})
        if exc_type is None:
            m.engine_steps += 1
            if chunks:
                m.prefill_steps += 1
        # (read again: the account's own time is the step's too)
        m.step_wall_ns += _now_ns() - self._t0
        return False


@dataclass
class RequestTimeline:
    """Wall-clock milestones of one request (perf_counter_ns)."""

    submitted_ns: int = 0
    admitted_ns: int = 0          # last admission (re-set on re-admit)
    first_admitted_ns: int = 0
    first_chunk_ns: int = 0       # first prefill chunk dispatched
    first_token_ns: int = 0
    finished_ns: int = 0
    tokens_generated: int = 0
    preemptions: int = 0
    finish_reason: Optional[str] = None

    def to_dict(self) -> dict:
        ttft = (self.first_token_ns - self.submitted_ns) / 1e9 \
            if self.first_token_ns else None
        queue_time = (self.admitted_ns - self.submitted_ns) / 1e9 \
            if self.admitted_ns else None
        # time-per-output-token over the decode phase (tokens after the
        # first, which prefill produced)
        tpot = None
        if self.finished_ns and self.tokens_generated > 1:
            tpot = ((self.finished_ns - self.first_token_ns) / 1e9
                    / (self.tokens_generated - 1))
        return {
            "ttft_s": ttft,
            "tpot_s": tpot,
            "queue_time_s": queue_time,
            "e2e_s": ((self.finished_ns - self.submitted_ns) / 1e9
                      if self.finished_ns else None),
            "tokens_generated": self.tokens_generated,
            "preemptions": self.preemptions,
            "finish_reason": self.finish_reason,
        }


class ServingMetrics:
    def __init__(self):
        # counters
        self.submitted = 0
        self.rejected = 0
        self.completed = 0          # every retirement, any finish_reason
        self.timed_out = 0          # retired past their deadline_s
        self.failed = 0             # retired with finish_reason "error"
        self.preempted = 0          # preemption EVENTS (re-admits recount)
        # tokens held by finished and live requests: raised as each
        # token is emitted, lowered by what a preemption drops
        self.tokens_generated = 0
        self.decode_iterations = 0
        self.prefills = 0
        # inside Engine.step(): host nanoseconds per phase (``phase()``)
        # and what the steps did
        self.step_ns = dict.fromkeys(PHASES, 0)
        self.engine_steps = 0
        # the step's account (``step()``): wall time, summed over steps;
        # the calling thread's CPU time, involuntary context switches and
        # minor page faults while it steps, summed over the readings of
        # ``_read_thread`` (``THREAD_READ_EVERY``: between two of them
        # lies the caller's turn between the steps too)
        self.step_wall_ns = 0
        self.step_cpu_ns = 0
        self._thread_read = None    # (wall clock, CPU clock, usage) then
        self._steps_unread = 0      # steps ended since
        self.step_nivcsw = 0
        self.step_minflt = 0
        # the engine's step programs (``TrackedFunction``s, which count
        # their calls and compiles), set by the engine that owns them
        self.programs = ()
        # host reads that wait for a program's result, and over them the
        # programs dispatched since the read before returned: the device
        # runs its programs in order, so that many the read may wait for
        self.blocking_reads = 0
        self.programs_behind_reads = 0
        self._dispatched_at_read = 0
        # slow steps (``SLOW_STEP_FACTOR``), their time beyond a usual
        # step's, and the last of their records
        self.slow_steps = 0
        self.slow_step_excess_ns = 0
        self.slow_step_log = deque(maxlen=SLOW_STEP_LOG)
        self._usual_ns = 0.0        # the usual step: mean of the last 64
        self._usual_seen = 0        # steps in it so far, 64 at most
        self._compiled_seen = 0     # programs_compiled() at the last step
        self._step_slots = 0        # decoding slots of the open step
        self._step_behind = 0       # largest programs_behind of its reads
        self.prefill_steps = 0          # steps that ran >= 1 chunk
        self.prefill_chunks_run = 0     # raised per chunk dispatched
        self.prefill_context_tokens = 0  # sum of start + tokens per chunk
        self.prefill_attended_pairs = 0  # (query, key) pairs chunks attend
        self.decode_context_tokens = 0  # sum of active lengths per step
        self.fetched_bytes = 0          # device results read on the host
        self.admissions = 0             # requests admitted a first time
        self.queue_wait_ns = 0          # first admission - submit
        self.lane_wait_ns = 0           # first chunk - first admission
        # a model that generates by diffusion over blocks (the engine's
        # block iteration; all stay 0 for a one-token decode)
        self.block_steps = 0            # runs of the block program
        self.block_slot_steps = 0       # live slots over those runs
        self.commit_slot_steps = 0      # of them, slots committing a block
        self.tokens_unmasked = 0        # positions a denoise step finalised
        self.block_context_tokens = 0   # sum of cached lengths per run
        # routed experts, counted on the device by every step program
        # that runs them (block steps and prefill chunks), summed over
        # layers: experts with at least one token, (token, expert)
        # assignments, and the busiest expert's assignments
        self.experts_read = 0
        self.expert_assignments = 0
        self.expert_assignments_max = 0
        # ... and of them what the one-token decode runs read (a chunk
        # reads nearly every expert, a decode run of a few rows far
        # fewer: one mean over both says nothing)
        self.experts_read_decode = 0
        self.expert_assignments_decode = 0
        # a model with window layers (serving/cache.py's window group;
        # all stay 0 where every layer is full): pages given back while
        # their request ran, and over the decode iterations the K/V
        # tokens a window layer's walk covers (min(context, window) a
        # running slot), the window-group pages live (a layer) and the
        # running slots
        self.window_pages_released = 0
        self.decode_window_tokens = 0
        self.window_pages_live = 0
        self.window_seq_steps = 0
        # prefix cache / chunked prefill
        self.prefix_cache_hits = 0      # admissions reusing >= 1 block
        self.prefix_cache_misses = 0    # admissions reusing none
        self.prefix_cache_evictions = 0
        self.prefill_chunks = 0
        self._cached_tokens_sum = 0
        self._prompt_tokens_sum = 0
        # overload control (serving/overload.py)
        self.shed = 0               # retired with finish_reason "shed"
        self.goodput_tokens = 0     # tokens from requests that BEAT
        #                             their deadline (or had none)
        self.watchdog_stalls = 0    # step attempts over the budget
        self.step_retries = 0       # watchdog retry attempts
        self.pool_lost = 0          # steps that failed holding the pool
        self.degradation_level = 0  # gauge: current ladder level
        self.health_state = 0       # gauge: 0 serving / 1 degraded / 2 failed
        # speculative decoding (serving/speculative.py)
        self.spec_tokens_drafted = 0    # draft proposals verified
        self.spec_tokens_accepted = 0   # proposals the target accepted
        # streaming (serving/stream.py): requests with an on_token
        # callback currently in flight
        self.stream_active = 0
        # quantized serving (kernels/kv_quant): numeric dtype code of
        # the engine's KV pool (0 fp32 / 1 int8 / 2 fp8) and the f32
        # scale-sidecar bytes one block carries (0 unquantized)
        self.kv_cache_dtype_code = 0
        self.kv_quant_scale_bytes = 0
        # gauge accumulators (sampled once per decode iteration)
        self._occupancy_sum = 0.0
        self._cache_util_sum = 0.0
        self._gauge_samples = 0
        self.last_batch_occupancy = 0.0
        self.last_cache_utilization = 0.0
        # per-request: live while the request is, then among the last
        # ``FINISHED_REQUESTS`` retired ones
        self.requests: Dict[str, RequestTimeline] = {}
        self.finished = deque(maxlen=FINISHED_REQUESTS)  # (id, to_dict())

    # handles are looked up per event (not cached) so a test calling
    # ``registry.clear()`` never leaves a mirror pointing at dead metrics
    @staticmethod
    def _obs():
        return _obsreg.get_registry() if _obsreg.enabled() else None

    # ------------------------------------------------------ step phases
    def phase(self, name: str, **metadata) -> _Phase:
        """Context manager around one phase of ``Engine.step()``
        (``name`` one of ``PHASES``): the span ``serving::<name>`` with
        ``metadata`` as its stats (a request id goes there, never into
        the name), and its host time into ``step_ns[name]``."""
        return _Phase(self, name, metadata)

    def step(self) -> _Step:
        """Context manager around one ``Engine.step()``, for the engine
        alone: the span ``serving::step`` (stat ``step``: the value of
        ``engine_steps`` as it began), the step's account into the
        ``step_*`` counters, ``engine_steps`` and ``prefill_steps``, and
        a record in ``slow_step_log`` where the step was slow."""
        return _Step(self)

    def _read_thread(self, now: int) -> dict:
        """Read the calling thread's CPU clock and usage at wall time
        ``now``, the end of a step, and add what they moved by since the
        last reading to the counters.  Returns that as a slow step's
        record has it: ``cpu_ns`` of the ``cpu_over_ns`` of wall time
        and ``cpu_over_steps`` steps, this one the last, since that
        reading (a thread that slept through a stall shows no more CPU
        time than its few usual steps take, one that was busy shows the
        stall's), and the switches and faults of the same stretch."""
        cpu, usage = _cpu_ns(), _thread_usage()
        then, cpu0, usage0 = self._thread_read or (now, cpu, usage)
        self._thread_read = (now, cpu, usage)
        steps, self._steps_unread = self._steps_unread, 0
        self.step_cpu_ns += cpu - cpu0
        self.step_nivcsw += usage.ru_nivcsw - usage0.ru_nivcsw
        self.step_minflt += usage.ru_minflt - usage0.ru_minflt
        return {"cpu_ns": cpu - cpu0, "cpu_over_ns": now - then,
                "cpu_over_steps": steps,
                "nvcsw": usage.ru_nvcsw - usage0.ru_nvcsw,
                "nivcsw": usage.ru_nivcsw - usage0.ru_nivcsw,
                "minflt": usage.ru_minflt - usage0.ru_minflt,
                "majflt": usage.ru_majflt - usage0.ru_majflt}

    def programs_dispatched(self) -> int:
        """Calls into the engine's step programs so far, every lane."""
        return sum(p.calls for p in self.programs)

    def programs_compiled(self) -> int:
        """Programs they compiled (or loaded from the compile cache)."""
        return sum(p.compiles for p in self.programs)

    def on_fetch(self, nbytes: int):
        """A step program's result of ``nbytes`` was read on the host,
        which waited for it: ``fetched_bytes`` over ``decode_iterations``
        is what a decode step sends to the host, 4 bytes a slot where
        the program chose the token."""
        self.fetched_bytes += nbytes
        dispatched = self.programs_dispatched()
        behind = dispatched - self._dispatched_at_read
        self._dispatched_at_read = dispatched
        self.blocking_reads += 1
        self.programs_behind_reads += behind
        if behind > self._step_behind:
            self._step_behind = behind

    def on_prefill_dispatch(self, request_id: str, start: int, tokens: int):
        """One chunk of ``tokens`` prompt tokens at positions ``start ..``
        is about to be dispatched for ``request_id``."""
        self.prefill_chunks_run += 1
        # the key positions the chunk kernel's walk covers
        self.prefill_context_tokens += start + tokens
        # the keys each REAL query token of the chunk sees, summed: the
        # whole context before the chunk, then itself and its
        # predecessors inside it
        self.prefill_attended_pairs += tokens * start \
            + tokens * (tokens + 1) // 2
        t = self.requests[request_id]
        if t.first_chunk_ns == 0:
            t.first_chunk_ns = _now_ns()
            self.lane_wait_ns += t.first_chunk_ns - t.first_admitted_ns

    # ------------------------------------------------------- lifecycle
    def on_submit(self, request_id: str):
        self.submitted += 1
        self.requests[request_id] = RequestTimeline(submitted_ns=_now_ns())
        reg = self._obs()
        if reg is not None:
            reg.counter("serving_requests_submitted_total",
                        "requests submitted to the engine").inc()

    def on_reject(self):
        self.rejected += 1
        reg = self._obs()
        if reg is not None:
            reg.counter("serving_requests_rejected_total",
                        "requests rejected at admission").inc()

    def on_admit(self, request_id: str):
        t = self.requests[request_id]
        was = t.admitted_ns
        t.admitted_ns = _now_ns()
        self.prefills += 1
        if was == 0:
            t.first_admitted_ns = t.admitted_ns
            self.admissions += 1
            self.queue_wait_ns += t.admitted_ns - t.submitted_ns
        reg = self._obs()
        if reg is not None:
            reg.counter("serving_prefills_total", "prefill passes").inc()
            if was == 0:
                reg.histogram(
                    "serving_queue_seconds",
                    "submit-to-first-admission wait").observe(
                        (t.admitted_ns - t.submitted_ns) / 1e9)

    def on_first_token(self, request_id: str):
        t = self.requests[request_id]
        if t.first_token_ns == 0:
            t.first_token_ns = _now_ns()
            reg = self._obs()
            if reg is not None:
                reg.histogram("serving_ttft_seconds",
                              "time to first token").observe(
                                  (t.first_token_ns - t.submitted_ns) / 1e9)

    def on_prefix_lookup(self, request_id: str, cached_tokens: int,
                         prompt_tokens: int):
        """One admission's prefix-cache outcome: how many of the
        prompt's tokens came from cached blocks (0 == miss)."""
        if cached_tokens > 0:
            self.prefix_cache_hits += 1
        else:
            self.prefix_cache_misses += 1
        self._cached_tokens_sum += cached_tokens
        self._prompt_tokens_sum += prompt_tokens
        reg = self._obs()
        if reg is not None:
            if cached_tokens > 0:
                reg.counter("serving_prefix_cache_hits_total",
                            "admissions reusing cached prefix blocks"
                            ).inc()
            else:
                reg.counter("serving_prefix_cache_misses_total",
                            "admissions with no cached prefix").inc()
            reg.gauge("serving_prefix_cached_token_ratio",
                      "prompt tokens served from the prefix cache, "
                      "cumulative ratio").set(
                          self._cached_tokens_sum
                          / max(self._prompt_tokens_sum, 1))

    def on_prefill_complete(self, request_id: str, chunks: int):
        """Prompt fully prefilled in ``chunks`` fixed-shape chunks."""
        self.prefill_chunks += chunks
        reg = self._obs()
        if reg is not None:
            reg.histogram("serving_prefill_chunks_per_request",
                          "prefill chunks per admitted prompt",
                          buckets=(1, 2, 4, 8, 16, 32, 64)
                          ).observe(chunks)

    def on_evictions(self, n: int):
        """``n`` cached blocks evicted from the pool's prefix LRU."""
        self.prefix_cache_evictions += n
        reg = self._obs()
        if reg is not None:
            reg.counter("serving_prefix_cache_evictions_total",
                        "prefix-cache blocks evicted (LRU)").inc(n)

    def on_preempt(self, request_id: str, dropped_tokens: int = 0):
        """``dropped_tokens``: what the victim had generated, which the
        re-admission computes (and emits) again."""
        self.preempted += 1
        self.tokens_generated -= dropped_tokens
        self.requests[request_id].preemptions += 1
        reg = self._obs()
        if reg is not None:
            reg.counter("serving_preemptions_total",
                        "requests preempted out of the batch").inc()

    def on_finish(self, request_id: str, tokens: int, reason: str):
        self.completed += 1
        if reason == "timeout":
            self.timed_out += 1
        elif reason == "error":
            self.failed += 1
        elif reason == "shed":
            self.shed += 1
        # (tokens_generated was raised as each of them was emitted)
        # goodput: tokens that were WORTH producing — the request
        # finished inside its SLO (timeouts/sheds/errors contribute 0)
        if reason in ("eos", "stop", "length"):
            self.goodput_tokens += tokens
        t = self.requests.pop(request_id)
        t.finished_ns = _now_ns()
        t.tokens_generated = tokens
        t.finish_reason = reason
        d = t.to_dict()
        self.finished.append((request_id, d))
        reg = self._obs()
        if reg is not None:
            reg.counter("serving_requests_completed_total",
                        "requests retired, by finish reason").inc(
                            reason=reason)
            if reason == "timeout":
                reg.counter("serving_requests_timed_out_total",
                            "requests retired past their deadline").inc()
            elif reason == "error":
                reg.counter("serving_requests_failed_total",
                            "requests retired with an error").inc()
            elif reason == "shed":
                reg.counter("serving_requests_shed_total",
                            "requests shed at admission (estimated TTFT "
                            "past the deadline)").inc()
            reg.counter("serving_tokens_generated_total",
                        "tokens produced by decode").inc(tokens)
            if reason in ("eos", "stop", "length"):
                reg.counter("serving_goodput_tokens_total",
                            "tokens from requests finished within "
                            "deadline").inc(tokens)
            if d["tpot_s"] is not None:
                reg.histogram("serving_tpot_seconds",
                              "time per output token (decode phase)"
                              ).observe(d["tpot_s"])
            if d["e2e_s"] is not None:
                reg.histogram("serving_e2e_seconds",
                              "submit-to-finish request latency"
                              ).observe(d["e2e_s"])

    # --------------------------------------------- speculative decoding
    def on_spec_commit(self, accepted_len: int):
        """One slot's verify outcome: ``accepted_len`` tokens committed
        this iteration (accepted drafts + the bonus/correction token,
        so 1..K+1)."""
        reg = self._obs()
        if reg is not None:
            reg.histogram("serving_accepted_per_step",
                          "tokens committed per request per speculative "
                          "verify step (accepted drafts + bonus)",
                          buckets=(1, 2, 3, 4, 5, 6, 8, 12, 16)
                          ).observe(accepted_len)

    def on_spec_step(self, drafted: int, accepted: int):
        """One speculative iteration over the bucket: ``drafted`` draft
        proposals verified, ``accepted`` of them kept.  The accept-rate
        gauge is cumulative — the bench's headline speculation signal."""
        self.spec_tokens_drafted += drafted
        self.spec_tokens_accepted += accepted
        reg = self._obs()
        if reg is not None:
            reg.counter("serving_spec_tokens_drafted_total",
                        "draft-model proposals verified by the target"
                        ).inc(drafted)
            reg.counter("serving_spec_tokens_accepted_total",
                        "draft proposals accepted by the target"
                        ).inc(accepted)
            reg.gauge("serving_spec_accept_rate",
                      "accepted / drafted speculative tokens, "
                      "cumulative").set(self.spec_accept_rate())

    def spec_accept_rate(self) -> float:
        return self.spec_tokens_accepted \
            / max(self.spec_tokens_drafted, 1)

    # -------------------------------------------------------- streaming
    def on_stream_start(self):
        self.stream_active += 1
        reg = self._obs()
        if reg is not None:
            reg.gauge("serving_stream_active",
                      "streaming requests currently in flight").set(
                          self.stream_active)

    def on_stream_end(self):
        self.stream_active -= 1
        reg = self._obs()
        if reg is not None:
            reg.gauge("serving_stream_active",
                      "streaming requests currently in flight").set(
                          self.stream_active)

    # ------------------------------------------------ overload control
    def on_watchdog_stall(self, label: str):
        """One step attempt ran past its watchdog budget."""
        self.watchdog_stalls += 1
        reg = self._obs()
        if reg is not None:
            reg.counter("serving_watchdog_stalls_total",
                        "compiled-step attempts over the watchdog "
                        "latency budget").inc(step=label)

    def on_step_retry(self, label: str):
        """One bounded-retry attempt after a step exception that left
        its operands live."""
        self.step_retries += 1
        reg = self._obs()
        if reg is not None:
            reg.counter("serving_step_retries_total",
                        "compiled-step retries (transient exception "
                        "before the program took its operands)"
                        ).inc(step=label)

    def on_pool_lost(self, label: str):
        """A step failed after it consumed its donated KV pool: the
        engine is FAILED until ``revive()`` rebuilds the pool."""
        self.pool_lost += 1
        reg = self._obs()
        if reg is not None:
            reg.counter("serving_pool_lost_total",
                        "compiled steps that failed after consuming "
                        "the donated KV pool").inc(step=label)

    def on_degradation_level(self, level: int):
        """Degradation ladder moved to ``level`` (0 = normal)."""
        self.degradation_level = level
        reg = self._obs()
        if reg is not None:
            reg.gauge("serving_degradation_level",
                      "memory-pressure degradation ladder level "
                      "(0 normal .. 4 preempt)").set(level)

    def on_health(self, code: int):
        """Engine health gauge (0 serving / 1 degraded / 2 failed)."""
        self.health_state = code
        reg = self._obs()
        if reg is not None:
            reg.gauge("serving_health_state",
                      "engine health (0 serving / 1 degraded / "
                      "2 failed)").set(code)

    def on_kv_cache_config(self, dtype_code: int, scale_bytes: int):
        """Engine construction reports its KV-pool storage format:
        ``dtype_code`` per kernels.kv_quant.KV_DTYPE_CODES (0 fp32 /
        1 int8 / 2 fp8), ``scale_bytes`` = f32 absmax sidecar bytes per
        block per (k or v) pool side."""
        self.kv_cache_dtype_code = int(dtype_code)
        self.kv_quant_scale_bytes = int(scale_bytes)
        reg = self._obs()
        if reg is not None:
            reg.gauge("serving_kv_cache_dtype",
                      "KV-pool storage dtype code (0 fp32 / 1 int8 / "
                      "2 fp8)").set(self.kv_cache_dtype_code)
            reg.gauge("kv_quant_scale_bytes",
                      "per-block f32 absmax scale sidecar bytes of one "
                      "quantized KV pool side (0 unquantized)").set(
                          self.kv_quant_scale_bytes)

    def on_decode_iteration(self, active: int, batch_size: int,
                            cache_utilization: float):
        self.decode_iterations += 1
        self._step_slots = active
        occ = active / batch_size if batch_size else 0.0
        self.last_batch_occupancy = occ
        self.last_cache_utilization = cache_utilization
        self._occupancy_sum += occ
        self._cache_util_sum += cache_utilization
        self._gauge_samples += 1
        reg = self._obs()
        if reg is not None:
            reg.counter("serving_decode_iterations_total",
                        "decode loop iterations").inc()
            reg.gauge("serving_batch_occupancy",
                      "active slots / batch size, last iteration").set(occ)
            reg.gauge("serving_cache_utilization",
                      "paged KV cache pages in use, last iteration").set(
                          cache_utilization)

    def on_block_step(self, active: int, committing: int,
                      context_tokens: int):
        """One run of the block program over ``active`` live slots, of
        which ``committing`` wrote their block's K/V."""
        self.block_steps += 1
        self.block_slot_steps += active
        self.commit_slot_steps += committing
        self.block_context_tokens += context_tokens

    def on_route_stats(self, experts_read: int, assignments: int,
                       assignments_max: int, decode: bool = False):
        """What one step program's routed layers read, from the device
        (``decode``: the program was a one-token decode run)."""
        self.experts_read += experts_read
        self.expert_assignments += assignments
        self.expert_assignments_max += assignments_max
        if decode:
            self.experts_read_decode += experts_read
            self.expert_assignments_decode += assignments

    def on_window_iteration(self, window_tokens: int, pages_live: int,
                            running: int):
        """One decode iteration of a model with window layers."""
        self.decode_window_tokens += window_tokens
        self.window_pages_live += pages_live
        self.window_seq_steps += running

    # --------------------------------------------------------- export
    def as_dict(self) -> dict:
        n = max(self._gauge_samples, 1)
        return {
            "counters": {
                "requests_submitted": self.submitted,
                "requests_rejected": self.rejected,
                "requests_completed": self.completed,
                "requests_timed_out": self.timed_out,
                "requests_failed": self.failed,
                "preemptions": self.preempted,
                "tokens_generated": self.tokens_generated,
                "decode_iterations": self.decode_iterations,
                "prefills": self.prefills,
                "prefix_cache_hits": self.prefix_cache_hits,
                "prefix_cache_misses": self.prefix_cache_misses,
                "prefix_cache_evictions": self.prefix_cache_evictions,
                "prefill_chunks": self.prefill_chunks,
                "requests_shed": self.shed,
                "goodput_tokens": self.goodput_tokens,
                "watchdog_stalls": self.watchdog_stalls,
                "step_retries": self.step_retries,
                "pool_lost": self.pool_lost,
                "spec_tokens_drafted": self.spec_tokens_drafted,
                "spec_tokens_accepted": self.spec_tokens_accepted,
                "engine_steps": self.engine_steps,
                "prefill_steps": self.prefill_steps,
                "prefill_chunks_run": self.prefill_chunks_run,
                "prefill_context_tokens": self.prefill_context_tokens,
                "prefill_attended_pairs": self.prefill_attended_pairs,
                "decode_context_tokens": self.decode_context_tokens,
                "fetched_bytes": self.fetched_bytes,
                "prompt_tokens": self._prompt_tokens_sum,
                "cached_prompt_tokens": self._cached_tokens_sum,
                "admissions": self.admissions,
                "queue_wait_ns": self.queue_wait_ns,
                "lane_wait_ns": self.lane_wait_ns,
                "block_steps": self.block_steps,
                "block_slot_steps": self.block_slot_steps,
                "commit_slot_steps": self.commit_slot_steps,
                "tokens_unmasked": self.tokens_unmasked,
                "block_context_tokens": self.block_context_tokens,
                "experts_read": self.experts_read,
                "expert_assignments": self.expert_assignments,
                "expert_assignments_max": self.expert_assignments_max,
                "experts_read_decode": self.experts_read_decode,
                "expert_assignments_decode": self.expert_assignments_decode,
                "window_pages_released": self.window_pages_released,
                "decode_window_tokens": self.decode_window_tokens,
                "window_pages_live": self.window_pages_live,
                "window_seq_steps": self.window_seq_steps,
                **{f"step_ns.{p}": ns for p, ns in self.step_ns.items()},
                "step_wall_ns": self.step_wall_ns,
                "step_cpu_ns": self.step_cpu_ns,
                "step_nivcsw": self.step_nivcsw,
                "step_minflt": self.step_minflt,
                "programs_dispatched": self.programs_dispatched(),
                "blocking_reads": self.blocking_reads,
                "programs_behind_reads": self.programs_behind_reads,
                "slow_steps": self.slow_steps,
                "slow_step_excess_ns": self.slow_step_excess_ns,
            },
            "gauges": {
                "degradation_level": self.degradation_level,
                "health_state": self.health_state,
                "spec_accept_rate": round(self.spec_accept_rate(), 4),
                "stream_active": self.stream_active,
                "batch_occupancy": self.last_batch_occupancy,
                "batch_occupancy_avg": round(self._occupancy_sum / n, 4),
                "cache_utilization": self.last_cache_utilization,
                "cache_utilization_avg": round(
                    self._cache_util_sum / n, 4),
                "prefix_cached_token_ratio": round(
                    self._cached_tokens_sum
                    / max(self._prompt_tokens_sum, 1), 4),
                "serving_kv_cache_dtype": self.kv_cache_dtype_code,
                "kv_quant_scale_bytes": self.kv_quant_scale_bytes,
            },
            "requests": {**dict(self.finished),
                         **{rid: t.to_dict()
                            for rid, t in self.requests.items()}},
            "slow_steps": [dict(r) for r in self.slow_step_log],
        }
