"""paddle_tpu.serving overload control — load shedding, the KV
memory-pressure degradation ladder, and the hung-step watchdog
(serving/overload.py), plus the H111 wall-clock-deadline scan.

The ISSUE 10 done bar lives here: under a seeded burst that produces
timeouts with shedding off, shedding on keeps every ADMITTED request
within its deadline at no goodput cost, the ladder engages and unwinds
deterministically, and an injected hung step is detected, retried, and
the engine returns to SERVING — all with constant compile counts.
"""
import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.resilience import ChaosError, FaultPlan
from paddle_tpu.resilience.chaos import burst_prompts
from paddle_tpu.serving import (DEGRADED, FAILED, LADDER_LEVELS, SERVING,
                                AdmissionError, Endpoint, Engine,
                                EngineQuarantined, Request, ServingConfig)
from paddle_tpu.serving.overload import DegradationLadder, LatencyEWMA
from paddle_tpu.serving.scheduler import PREFILLING, QUEUED, Scheduler


# Shared compiled steps: one model for the module (same pattern as
# test_serving.py) so engines reuse cached executables.
@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = LlamaForCausalLM(LlamaConfig.tiny())
    m.eval()
    return m


def _prompts(lengths, vocab=256, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, vocab, size=(L,)).astype(np.int32)
            for L in lengths]


def _reference(model, prompt, **kw):
    out = model.generate(paddle.to_tensor(prompt[None, :]),
                         temperature=0.0, use_static_cache=True, **kw)
    return np.asarray(out.numpy())[0]


def _config(**kw):
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 64)
    kw.setdefault("max_queue_len", 16)
    kw.setdefault("chunk_tokens", 4)
    return ServingConfig(**kw)


def _warm(model, eng, prompt_len=8, max_new=4):
    """One drained request: warms both latency EWMAs (first sample per
    step is recorded as compile time and excluded)."""
    (p,) = _prompts([prompt_len], seed=42)
    eng.generate([p], max_new_tokens=max_new)
    assert eng.overload.chunk_ewma.warmed
    assert eng.overload.decode_ewma.warmed


# ---------------------------------------------------------------------------
# LatencyEWMA
# ---------------------------------------------------------------------------

class TestLatencyEWMA:
    def test_first_sample_is_compile_and_excluded(self):
        e = LatencyEWMA(alpha=0.2)
        assert not e.warmed
        e.observe(9.0)                    # the XLA compile
        assert e.compile_s == 9.0 and e.value is None and not e.warmed
        e.observe(1.0)
        assert e.warmed and e.value == 1.0

    def test_ewma_update(self):
        e = LatencyEWMA(alpha=0.2)
        e.observe(5.0)                    # compile, dropped
        e.observe(1.0)
        e.observe(2.0)
        assert e.value == pytest.approx(0.2 * 2.0 + 0.8 * 1.0)
        assert e.samples == 2


# ---------------------------------------------------------------------------
# deadline-aware load shedding
# ---------------------------------------------------------------------------

class TestLoadShedding:
    def test_cold_engine_never_sheds(self, model):
        """A fresh engine has no latency basis: even a deadline of 0
        must be ADMITTED (and then time out) rather than shed."""
        eng = Engine(model, _config())
        assert not eng.overload.can_estimate()
        (p,) = _prompts([6])
        req = eng.submit(p, max_new_tokens=4, deadline_s=0.0)
        assert req.state == QUEUED        # admitted, not shed
        eng.run_until_complete()
        assert req.finish_reason == "timeout"
        assert eng.stats()["counters"]["requests_shed"] == 0

    def test_warm_engine_sheds_hopeless_deadline(self, model):
        eng = Engine(model, _config())
        _warm(model, eng)
        # a backlog the estimator must see: 3 waiting prompts
        backlog = [eng.submit(p, max_new_tokens=4)
                   for p in _prompts([12, 12, 12], seed=1)]
        (p,) = _prompts([12], seed=2)
        est = eng.overload.estimate_ttft_s(eng, p)
        assert est > 0.001                # 12+ chunks of real latency
        shed = eng.submit(p, max_new_tokens=4, deadline_s=0.001)
        assert shed.finish_reason == "shed"
        assert shed.state == "finished" and shed.num_generated == 0
        # a generous deadline with the SAME backlog is admitted
        ok = eng.submit(p, max_new_tokens=4, deadline_s=3600.0)
        assert ok.state == QUEUED
        done = eng.run_until_complete()
        assert shed.request_id in done    # shed requests are reported
        for r in backlog + [ok]:
            assert r.finish_reason == "length"
        c = eng.stats()["counters"]
        assert c["requests_shed"] == 1
        assert c["requests_timed_out"] == 0
        # goodput counts only useful completions, never the shed
        assert c["goodput_tokens"] == sum(
            r.num_generated for r in backlog + [ok]) + 4
        eng.pool.check_leaks()

    def test_shedding_disabled_admits_and_times_out(self, model):
        eng = Engine(model, _config(enable_load_shedding=False))
        _warm(model, eng)
        for p in _prompts([12, 12, 12], seed=1):
            eng.submit(p, max_new_tokens=4)
        (p,) = _prompts([12], seed=2)
        req = eng.submit(p, max_new_tokens=4, deadline_s=0.001)
        assert req.state == QUEUED        # no estimate consulted
        eng.run_until_complete()
        assert req.finish_reason == "timeout"
        assert eng.stats()["counters"]["requests_shed"] == 0

    def test_full_queue_sheds_lower_priority(self, model):
        eng = Engine(model, _config(max_queue_len=2))
        lo = [eng.submit(p, max_new_tokens=2, priority=0)
              for p in _prompts([6, 6], seed=3)]
        # same priority hitting the full queue: plain rejection
        (p,) = _prompts([6], seed=4)
        with pytest.raises(AdmissionError, match="wait queue full"):
            eng.submit(p, max_new_tokens=2, priority=0)
        # higher priority displaces the youngest low-priority waiter
        hi = eng.submit(p, max_new_tokens=2, priority=5)
        assert hi.state == QUEUED
        assert lo[1].finish_reason == "shed"   # youngest victim
        assert lo[0].state == QUEUED
        eng.run_until_complete()
        assert hi.finish_reason == "length"
        assert eng.stats()["counters"]["requests_shed"] == 1
        eng.pool.check_leaks()


class TestPriorityPolicy:
    def _req(self, priority):
        return Request(prompt=np.asarray([1, 2], np.int32),
                       priority=priority)

    def test_pick_victim_lowest_priority_youngest(self):
        s = Scheduler(pool=None)
        a, b, c = self._req(1), self._req(0), self._req(0)
        s.running = [a, b, c]
        assert s.pick_victim() is c       # lowest class, youngest in it

    def test_shed_candidate_strictly_lower_only(self):
        s = Scheduler(pool=None)
        a, b = self._req(1), self._req(1)
        s.waiting.extend([a, b])
        assert s.shed_candidate(1) is None        # same class: reject
        assert s.shed_candidate(2) is b           # youngest of lowest

    def test_admission_prefers_high_priority(self, model):
        eng = Engine(model, _config(max_batch_size=1))
        lo = eng.submit(_prompts([6], seed=5)[0], max_new_tokens=2,
                        priority=0)
        hi = eng.submit(_prompts([6], seed=6)[0], max_new_tokens=2,
                        priority=3)
        eng.step()                        # one admission decision
        assert hi.state != QUEUED         # jumped the older low request
        assert lo.state == QUEUED
        eng.run_until_complete()
        assert lo.finish_reason == "length"


# ---------------------------------------------------------------------------
# degradation ladder
# ---------------------------------------------------------------------------

class _FakeMetrics:
    def __init__(self):
        self.levels = []

    def on_degradation_level(self, level):
        self.levels.append(level)


class _FakeEngine:
    class _Pool:
        pressure = 0.0
        evict_calls = 0

        def utilization(self):
            return self.pressure

        def byte_utilization(self):
            # the ladder is byte-denominated (dtype-aware); the fake
            # has no dtype split, so both views agree
            return self.pressure

        def evict_parked(self, n=None):
            self.evict_calls += 1
            return 0

    class _Sched:
        def __init__(self):
            self.running = []

        def pick_victim(self):
            return self.running[-1] if self.running else None

    def __init__(self):
        self.pool = self._Pool()
        self.scheduler = self._Sched()
        self.preempted = []

    def _preempt(self, victim):
        self.preempted.append(victim)
        self.scheduler.running.remove(victim)


class TestDegradationLadder:
    def test_watermark_validation(self):
        with pytest.raises(ValueError, match="watermarks"):
            DegradationLadder(_FakeMetrics(), high=0.3, low=0.5)

    def test_escalates_and_unwinds_one_level_per_tick(self):
        m = _FakeMetrics()
        ladder = DegradationLadder(m, high=0.5, low=0.3)
        eng = _FakeEngine()
        eng.scheduler.running = ["a", "b", "c"]
        eng.pool.pressure = 0.9
        levels = [ladder.tick(eng) for _ in range(6)]
        assert levels == [1, 2, 3, 4, 4, 4]       # capped at preempt
        assert ladder.level_name == "preempt"
        assert ladder.admissions_paused
        assert ladder.effective_prefill_budget(256) == 1
        # preempt fires every tick at the top level, never on the sole
        # running request
        assert eng.preempted == ["c", "b"]
        assert eng.scheduler.running == ["a"]
        assert eng.pool.evict_calls == 6          # every tick >= level 1
        # hysteresis band: no movement between the watermarks
        eng.pool.pressure = 0.4
        assert ladder.tick(eng) == 4
        # drop below low: unwind retraces the rungs
        eng.pool.pressure = 0.1
        levels = [ladder.tick(eng) for _ in range(5)]
        assert levels == [3, 2, 1, 0, 0]
        assert not ladder.admissions_paused
        assert ladder.effective_prefill_budget(256) == 256
        # the gauge saw every transition, in order
        assert m.levels == [1, 2, 3, 4, 3, 2, 1, 0]
        steps = list(zip([0] + m.levels, m.levels))
        assert all(abs(b - a) == 1 for a, b in steps)

    def test_burst_engages_and_unwinds_on_real_engine(self, model):
        """Satellite: deterministic chaos burst against explicit
        watermarks — levels advance in order, counters move, the
        ladder unwinds, and nothing retraces."""
        eng = Engine(model, _config(
            num_blocks=16, max_batch_size=4, max_queue_len=32,
            kv_high_watermark=0.5, kv_low_watermark=0.3))
        # compile both steps before the burst (the jit cache is shared
        # across engine configs, so the absolute size is not 1 here —
        # what must hold is that the ladder episode adds nothing)
        _warm(model, eng)
        sizes = (eng.decode_cache_size(), eng.prefill_cache_size())
        burst = burst_prompts(seed=5, n=8, min_len=8, max_len=16)
        reqs = [eng.submit(p, max_new_tokens=4) for p in burst]
        done = eng.run_until_complete()
        assert len(done) == 8
        for r in reqs:                    # no deadlines: all complete
            assert r.finish_reason == "length"
        ladder = eng.overload.ladder
        levels = [lvl for _, lvl in ladder.transitions]
        assert levels, "burst never engaged the ladder"
        # one level per tick, starting from normal
        steps = list(zip([0] + levels, levels))
        assert all(abs(b - a) == 1 for a, b in steps)
        assert max(levels) >= LADDER_LEVELS.index("pause_admissions")
        c = eng.stats()["counters"]
        assert c["preemptions"] > 0       # pressure actions fired
        # drained engine: idle ticks unwind back to normal
        for _ in range(len(LADDER_LEVELS)):
            eng.step()
        assert ladder.level == 0
        assert eng.stats()["gauges"]["degradation_level"] == 0
        # the no-retrace contract survived the whole episode
        assert eng._decode_step.retraces == 0
        assert eng._prefill_step.retraces == 0
        assert (eng.decode_cache_size(), eng.prefill_cache_size()) \
            == sizes
        eng.pool.check_leaks()


# ---------------------------------------------------------------------------
# hung-step watchdog
# ---------------------------------------------------------------------------

class TestWatchdog:
    def test_stall_detected_degraded_then_recovers(self, model):
        eng = Engine(model, _config(
            watchdog_floor_s=0.25, watchdog_budget_mult=50.0,
            step_max_retries=1, health_recovery_steps=2))
        (p,) = _prompts([4], seed=7)
        req = eng.submit(p, max_new_tokens=6)
        # attempt ordinals count prefill+decode including retries:
        # 1 = the prefill chunk, 3 = the second decode attempt
        with FaultPlan(step_delay_s={3: 0.6}) as plan:
            eng.run_until_complete()
        assert ("serving_delay", 3, "serving::decode_step") \
            in plan.injected
        assert req.finish_reason == "length"
        np.testing.assert_array_equal(
            req.output_ids(), _reference(model, p, max_new_tokens=6))
        # a step that returned late has advanced the (donated) pool: its
        # result is kept and nothing is dispatched again
        wd = eng.overload.decode_watchdog
        assert wd.stalls == 1 and wd.retries == 0
        c = eng.stats()["counters"]
        assert c["watchdog_stalls"] == 1 and c["step_retries"] == 0
        assert c["pool_lost"] == 0
        assert plan._serving_step_calls == 1 + 5   # a chunk, five decodes
        # DEGRADED was entered on the stall, then self-healed after
        # health_recovery_steps clean steps
        assert eng.health()["state"] == SERVING
        assert eng.stats()["gauges"]["health_state"] == 0

    def test_transient_step_failure_retried(self, model):
        eng = Engine(model, _config(step_retry_backoff_s=0.01))
        (p,) = _prompts([8], seed=8)
        req = eng.submit(p, max_new_tokens=4)
        # ordinal 2 = the second prefill chunk; its retry (ordinal 3)
        # is not scheduled to fail, so the engine absorbs the fault
        with FaultPlan(fail_step_at={2}) as plan:
            eng.run_until_complete()
        assert ("serving_fail", 2, "serving::prefill_step") \
            in plan.injected
        assert req.finish_reason == "length"
        np.testing.assert_array_equal(
            req.output_ids(), _reference(model, p, max_new_tokens=4))
        assert eng.health()["state"] == SERVING
        assert eng.stats()["counters"]["step_retries"] >= 1
        assert eng._prefill_step.retraces == 0

    def test_exhausted_retries_quarantine_and_revive(self, model):
        eng = Engine(model, _config(step_max_retries=1,
                                    step_retry_backoff_s=0.01))
        (p,) = _prompts([8], seed=9)
        req = eng.submit(p, max_new_tokens=4)
        # consecutive failures exhaust max_retries+1 attempts
        with FaultPlan(fail_step_at={1, 2}):
            with pytest.raises(EngineQuarantined):
                eng.run_until_complete()
        h = eng.health()
        assert h["state"] == FAILED
        assert "ChaosError" in h["last_error"]
        # quarantined: no new work, stepping refuses too
        with pytest.raises(AdmissionError, match="quarantined"):
            eng.submit(_prompts([4], seed=10)[0], max_new_tokens=2)
        with pytest.raises(EngineQuarantined):
            eng.step()
        # operator revive: the stranded request resumes and completes
        eng.revive()
        assert eng.health()["state"] == SERVING
        eng.run_until_complete()
        assert req.finish_reason == "length"
        np.testing.assert_array_equal(
            req.output_ids(), _reference(model, p, max_new_tokens=4))
        eng.pool.check_leaks()

    @pytest.mark.parametrize("attempt, label, sampled", [
        (2, "serving::prefill_step", False),      # the second chunk
        (4, "serving::decode_step", False),       # the second decode
        (4, "serving::sampled_decode_step", True),
    ])
    def test_fault_after_dispatch_loses_the_pool_and_revive_rebuilds_it(
            self, model, attempt, label, sampled):
        """A step that fails once its call returned has consumed the
        donated pool: no retry can run.  FAILED at once, ``pool_lost``
        counted; ``revive()`` gives the pool fresh buffers and the
        stranded request is recomputed to the tokens of a clean run."""
        eng = Engine(model, _config(step_max_retries=2,
                                    step_retry_backoff_s=0.01))
        (p,) = _prompts([8], seed=12)
        kw = dict(temperature=0.7, seed=5) if sampled else {}
        # a clean engine's tokens (sampling is a function of the seed)
        clean = Engine(model, _config())
        want = clean.generate([p], max_new_tokens=4, **kw)[0]
        req = eng.submit(p, max_new_tokens=4, **kw)
        with FaultPlan(fail_after_dispatch_at={attempt}) as plan:
            with pytest.raises(EngineQuarantined, match="took its pool"):
                eng.run_until_complete()
        assert ("serving_fail_after_dispatch", attempt, label) \
            in plan.injected
        assert plan._serving_step_calls == attempt      # no retry ran
        h = eng.health()
        assert h["state"] == FAILED and "ChaosError" in h["last_error"]
        c = eng.stats()["counters"]
        assert c["pool_lost"] == 1 and c["step_retries"] == 0
        assert eng.pool.lost()
        with pytest.raises(EngineQuarantined):
            eng.step()
        eng.revive()
        assert eng.health()["state"] == SERVING
        assert not eng.pool.lost()
        assert eng.pool.num_cached == 0 and not eng.pool._hash_index
        assert req.preemptions == 1
        eng.run_until_complete()
        assert req.finish_reason == "length"
        np.testing.assert_array_equal(req.output_ids(), want)
        if not sampled:
            np.testing.assert_array_equal(
                want, _reference(model, p, max_new_tokens=4))
        eng.pool.check_leaks()
        assert eng._decode_step.retraces == 0
        assert eng._prefill_step.retraces == 0

    def test_revive_with_the_pool_intact_keeps_it(self, model):
        """Retries exhausted before any program ran: the operands are
        live, ``revive()`` touches neither the pool nor the requests."""
        eng = Engine(model, _config(step_max_retries=0))
        (p,) = _prompts([8], seed=13)
        req = eng.submit(p, max_new_tokens=3)
        with FaultPlan(fail_step_at={2}):
            with pytest.raises(EngineQuarantined):
                eng.run_until_complete()
        before = [id(a) for entry in eng.pool.layers for a in entry]
        eng.revive()
        assert [id(a) for entry in eng.pool.layers for a in entry] == before
        assert req.preemptions == 0
        assert eng.stats()["counters"]["pool_lost"] == 0
        eng.run_until_complete()
        np.testing.assert_array_equal(
            req.output_ids(), _reference(model, p, max_new_tokens=3))

    def test_endpoint_health_snapshot(self, model):
        ep = Endpoint(model, _config())
        h = ep.health()
        assert h["state"] == SERVING
        for key in ("degradation_level", "admissions_paused",
                    "watchdog_stalls", "step_retries", "queue_depth",
                    "kv_pressure", "last_error"):
            assert key in h


# ---------------------------------------------------------------------------
# exactly-once block release: deadline expiry mid-PREFILLING on a
# prefix-cache hit (shared blocks must survive, nothing double-freed)
# ---------------------------------------------------------------------------

class TestMidPrefillExpiry:
    def test_expiry_mid_prefill_with_prefix_hit(self, model):
        eng = Engine(model, _config(num_blocks=32, max_batch_size=2))
        (big,) = _prompts([24], seed=11)
        head = big[:8]
        # park a 2-block prefix
        first = eng.submit(head, max_new_tokens=2)
        eng.run_until_complete()
        assert first.finish_reason == "length"
        hits_before = eng.metrics.prefix_cache_hits
        # the long request matches the parked prefix, then expires
        # BETWEEN prefill chunks
        req = eng.submit(big, max_new_tokens=4, deadline_s=3600.0)
        eng.step()
        assert req.state == PREFILLING
        assert req.cached_tokens >= 8
        assert eng.metrics.prefix_cache_hits > hits_before
        req.deadline_t = time.monotonic() - 1.0   # force expiry
        eng.run_until_complete()
        assert req.finish_reason == "timeout"
        # exactly-once release: nothing leaked (and a double free would
        # have raised inside _retire)
        eng.pool.check_leaks()
        # the SHARED prefix blocks survived the release and still serve
        hits_mid = eng.metrics.prefix_cache_hits
        again = eng.submit(head, max_new_tokens=2)
        eng.run_until_complete()
        assert again.finish_reason == "length"
        assert eng.metrics.prefix_cache_hits > hits_mid
        eng.pool.check_leaks()


# ---------------------------------------------------------------------------
# H111: wall-clock deadlines
# ---------------------------------------------------------------------------

class TestH111WallClockDeadlines:
    def _scan_src(self, tmp_path, src):
        from paddle_tpu.analysis import scan_wall_clock_deadlines

        p = os.path.join(str(tmp_path), "mod.py")
        with open(p, "w") as f:
            f.write(src)
        return scan_wall_clock_deadlines(p)

    def test_flags_deadline_armed_from_wall_clock(self, tmp_path):
        diags = self._scan_src(tmp_path, (
            "import time\n"
            "def arm(timeout_s):\n"
            "    deadline = time.time() + timeout_s\n"
            "    return deadline\n"))
        assert [d.code for d in diags] == ["H111"]
        assert diags[0].severity == "error"

    def test_bare_timestamp_is_a_warning(self, tmp_path):
        diags = self._scan_src(tmp_path, (
            "import time\n"
            "def label():\n"
            "    stamp = time.time()\n"
            "    return stamp\n"))
        assert len(diags) == 1 and diags[0].severity == "warning"

    def test_monotonic_is_clean(self, tmp_path):
        diags = self._scan_src(tmp_path, (
            "import time\n"
            "def arm(timeout_s):\n"
            "    return time.monotonic() + timeout_s\n"))
        assert diags == []

    def test_serving_and_resilience_are_clean(self):
        """The deadline/watchdog layers must be monotonic-clock only —
        not even timestamp WARNINGs are tolerated there."""
        import paddle_tpu
        from paddle_tpu.analysis import scan_wall_clock_deadlines

        root = os.path.dirname(paddle_tpu.__file__)
        diags = scan_wall_clock_deadlines(
            [os.path.join(root, "serving"),
             os.path.join(root, "resilience")])
        assert diags == [], diags


# ---------------------------------------------------------------------------
# acceptance: the seeded overload burst, shedding on vs off
# ---------------------------------------------------------------------------

class TestOverloadAcceptance:
    DELAY_S = 0.03
    DEADLINE_S = 0.7

    def _burst_run(self, model, shed_on):
        """Identical seeded burst + injected slowdown, shedding
        toggled.  One small feasible request, then four requests whose
        prefill alone (24+ chunks x the injected delay) can never meet
        the deadline on ANY machine."""
        eng = Engine(model, _config(
            num_blocks=64, max_batch_size=4, max_queue_len=32,
            enable_load_shedding=shed_on))
        with FaultPlan(seed=11, step_delay_s=self.DELAY_S):
            _warm(model, eng)             # EWMAs absorb the slowdown
            sizes = (eng.decode_cache_size(), eng.prefill_cache_size())
            feasible = _prompts([8], seed=12)
            doomed = burst_prompts(seed=11, n=4, min_len=96, max_len=96)
            reqs = [eng.submit(p, max_new_tokens=4,
                               deadline_s=self.DEADLINE_S)
                    for p in feasible + doomed]
            eng.run_until_complete()
        return eng, reqs, sizes

    def test_shedding_keeps_admitted_requests_within_deadline(self, model):
        eng_off, reqs_off, sizes_off = self._burst_run(model,
                                                       shed_on=False)
        eng_on, reqs_on, sizes_on = self._burst_run(model, shed_on=True)
        c_off = eng_off.stats()["counters"]
        c_on = eng_on.stats()["counters"]

        # shedding OFF: the hopeless requests were admitted, burned
        # prefill work, and timed out
        assert c_off["requests_shed"] == 0
        assert c_off["requests_timed_out"] == 4
        assert reqs_off[0].finish_reason == "length"

        # shedding ON: the same requests are rejected at admission;
        # every ADMITTED request finishes within its deadline
        assert c_on["requests_shed"] == 4
        assert c_on["requests_timed_out"] == 0
        for r in reqs_on:
            assert r.finish_reason in ("length", "shed")
            if r.finish_reason == "shed":
                assert r.num_generated == 0
        assert reqs_on[0].finish_reason == "length"

        # goodput: shedding never costs useful tokens, and never burns
        # MORE prefill than admitting doomed work does
        assert c_on["goodput_tokens"] >= c_off["goodput_tokens"]
        assert c_on["prefill_chunks"] <= c_off["prefill_chunks"]

        # identical greedy output for the surviving request
        np.testing.assert_array_equal(reqs_on[0].output_ids(),
                                      reqs_off[0].output_ids())

        # constant compile counts: overload control adds zero retraces
        # and no new executables after warmup, shedding on or off
        for eng, sizes in ((eng_on, sizes_on), (eng_off, sizes_off)):
            assert eng._decode_step.retraces == 0
            assert eng._prefill_step.retraces == 0
            assert (eng.decode_cache_size(),
                    eng.prefill_cache_size()) == sizes
            assert eng.health()["state"] == SERVING
            eng.pool.check_leaks()
