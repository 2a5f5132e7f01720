"""Spans and counters inside ``Engine.step()``, the one span primitive
(``profiler.RecordEvent``), and the names the step programs and the
model's parts carry into a trace (ISSUE 26); the step's own span and
account, its log of slow steps and the bound on the timelines kept
(ISSUE 38)."""
import glob
import os
import re
import time

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.resilience.chaos import FaultPlan
from paddle_tpu.serving import Engine, ServingConfig
from paddle_tpu.serving import metrics as serving_metrics
from paddle_tpu.serving.metrics import PHASES

NEW_COUNTERS = (
    "engine_steps", "prefill_steps", "prefill_chunks_run",
    "prefill_context_tokens", "decode_context_tokens", "prompt_tokens", "cached_prompt_tokens",
    "admissions", "queue_wait_ns", "lane_wait_ns")
STEP_COUNTERS = (
    "step_wall_ns", "step_cpu_ns", "step_nivcsw",
    "step_minflt", "programs_dispatched", "blocking_reads",
    "programs_behind_reads", "slow_steps", "slow_step_excess_ns")
RECORD_FIELDS = {
    "step", "start_ns", "wall_ns", "usual_ns", "cpu_ns", "cpu_over_ns",
    "cpu_over_steps", "phase_ns", "unowned_ns", "chunks", "slots", "programs_behind",
    "nvcsw", "nivcsw", "minflt", "majflt", "gc_collections",
    "programs_compiled"}
SPANS = {"serving::" + p for p in PHASES}
MODEL_SCOPES = ("embed", "attn_qkv", "kv_write", "attn", "attn_out", "mlp",
                "final_norm", "lm_head")


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = LlamaForCausalLM(LlamaConfig.tiny())
    m.eval()
    return m


def _engine(model, **kw):
    cfg = dict(max_batch_size=2, block_size=4, num_blocks=64, chunk_tokens=4,
               enable_prefix_cache=False)
    cfg.update(kw)
    return Engine(model, ServingConfig(**cfg))


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(1, 256, size=(n,)).astype(
        np.int32)


def test_every_new_counter_is_there_and_zero_on_a_fresh_engine(model):
    stats = _engine(model).stats()
    counters = stats["counters"]
    names = NEW_COUNTERS + STEP_COUNTERS \
        + tuple(f"step_ns.{p}" for p in PHASES)
    assert len(PHASES) == 8
    for name in names:
        assert counters[name] == 0 and type(counters[name]) is int, name
    # a window's change can be taken of every key from the start
    assert all(isinstance(v, (int, float)) and not isinstance(v, bool)
               for v in counters.values())
    assert stats["slow_steps"] == []


def test_counters_of_a_workload_worked_out_by_hand(model):
    """Two slots, chunks of 4, one chunk a step.  A has 6 prompt tokens,
    B has 3, three new tokens each:
    step 1 admits both, A's chunk 1 (4 tokens), nothing decodes;
    step 2 A's chunk 2 (2 tokens) and first token, decode A at length 6;
    step 3 B's chunk (3 tokens) and first token, decode A (7) and B (3),
           A ends;
    step 4 decode B (4), B ends."""
    eng = _engine(model)
    eng.submit(_prompt(6, 1), max_new_tokens=3)
    eng.submit(_prompt(3, 2), max_new_tokens=3)
    timeline = eng.metrics.requests["req-0"]     # live until it retires
    steps, behind = 0, []
    while eng.has_work():
        before = eng.metrics.programs_behind_reads
        eng.step()
        steps += 1
        behind.append(eng.metrics.programs_behind_reads - before)
    c = eng.metrics.as_dict()["counters"]
    assert steps == c["engine_steps"] == 4
    # the device runs its programs in order: A's first-token read has
    # both of A's chunks before it, every other read the one program
    # dispatched since the read before it returned
    assert behind == [0, 2 + 1, 1 + 1, 1]
    assert c["programs_dispatched"] == 3 + 3 == c["programs_behind_reads"]
    assert c["blocking_reads"] == 2 + 3
    assert c["prefill_steps"] == 3 and c["prefill_chunks_run"] == 3
    # each chunk's start + tokens: the keys its kernel's walk covers
    assert c["prefill_context_tokens"] == (0 + 4) + (4 + 2) + (0 + 3)
    # every real query token sees the context before its chunk, itself
    # and its predecessors inside the chunk
    assert c["prefill_attended_pairs"] == 10 + (2 * 4 + 3) + 6
    assert c["prompt_tokens"] == 9
    assert c["cached_prompt_tokens"] == 0
    assert c["decode_iterations"] == 3
    assert c["decode_context_tokens"] == 6 + (7 + 3) + 4
    assert c["admissions"] == 2
    assert c["tokens_generated"] == 6 == c["goodput_tokens"]
    assert c["prefill_chunks"] == 3          # the old counter, at completion
    # A's chunks came first: B waited on the lane, A did not queue
    assert c["lane_wait_ns"] > 0 and c["queue_wait_ns"] >= 0
    assert all(c[f"step_ns.{p}"] > 0 for p in PHASES)
    assert not eng.metrics.requests               # both have retired
    assert timeline.submitted_ns <= timeline.first_admitted_ns \
        <= timeline.first_chunk_ns <= timeline.first_token_ns


def test_the_phases_cover_the_step(model):
    """What no phase owns is the watchdogs' bookkeeping between the
    spans, some 60 us a step whatever the model: a tenth of this toy's
    0.6 ms step, 0.03 % of a 180 ms one."""
    eng = _engine(model)
    eng.generate([_prompt(5)], max_new_tokens=2)      # compiles
    before = sum(eng.metrics.step_ns.values())
    account_before = eng.metrics.step_wall_ns
    for n in (6, 9, 3):
        eng.submit(_prompt(n, n), max_new_tokens=6)
    wall = 0
    while eng.has_work():
        t0 = time.perf_counter_ns()
        eng.step()
        wall += time.perf_counter_ns() - t0
    inside = sum(eng.metrics.step_ns.values()) - before
    assert 0.8 * wall <= inside <= wall
    # the step's own clock covers the call but for a few microseconds
    account = eng.metrics.step_wall_ns - account_before
    assert inside <= account <= wall and account >= 0.98 * wall


def test_tokens_generated_rises_with_every_token(model):
    eng = _engine(model)
    seen = []
    reqs = [eng.submit(_prompt(n, n), max_new_tokens=4,
                       on_token=seen.append) for n in (6, 3, 5)]
    while eng.has_work():
        eng.step()
        assert eng.metrics.tokens_generated == len(seen)
    c = eng.metrics.as_dict()["counters"]
    assert c["tokens_generated"] == sum(r.num_generated for r in reqs) == 12


def test_a_preemption_takes_its_dropped_tokens_back(model):
    """Recompute mode drops what the victim generated and emits it
    again: the counter stays the tokens that requests hold."""
    eng = _engine(model, num_blocks=8)       # 7 usable blocks of 4
    reqs = [eng.submit(_prompt(9, n), max_new_tokens=8) for n in (1, 2)]
    eng.run_until_complete()
    c = eng.metrics.as_dict()["counters"]
    assert c["preemptions"] >= 1
    assert c["tokens_generated"] == sum(r.num_generated for r in reqs) == 16


def _host_spans(trace_dir):
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if "::" in ev.name and ev.name.split("::")[0] == "serving":
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name, dict(ev.stats)))
    return sorted(out, key=lambda e: e[:2])


def _traced(eng, tmp_path, submit):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        submit()
        eng.run_until_complete()
    finally:
        jax.profiler.stop_trace()
    return _host_spans(str(tmp_path))


def test_a_jax_trace_holds_the_eight_spans_flat_and_with_fixed_names(
        model, tmp_path):
    eng = _engine(model)
    eng.generate([_prompt(5)], max_new_tokens=2)      # compiles
    spans = _traced(eng, tmp_path, lambda: [
        eng.submit(_prompt(6, 1), max_new_tokens=3, request_id="alpha"),
        eng.submit(_prompt(3, 2), max_new_tokens=3, request_id="beta")])
    steps = [sp for sp in spans if sp[2] == "serving::step"]
    spans = [sp for sp in spans if sp[2] != "serving::step"]
    names = [name for _, _, name, _ in spans]
    # the four steps worked out above: one span a phase a step it ran in
    assert {n: names.count("serving::" + n) for n in PHASES} == {
        "admit": 4, "prefill_dispatch": 3, "first_token": 2,
        "decode_prepare": 3, "decode_dispatch": 3, "decode_fetch": 3,
        "sample_emit": 3, "pool_sync": 4}
    for (_, end, name, _), (start, _, nxt, _) in zip(spans, spans[1:]):
        assert start >= end, (name, nxt)      # flat among themselves
    # one span a step, numbered from the engine's count as it began (the
    # compiling request took the steps before), and every phase inside
    # exactly one of them
    first = steps[0][3]["step"]
    assert [st["step"] for _, _, _, st in steps] \
        == [first, first + 1, first + 2, first + 3]
    assert first == eng.metrics.engine_steps - 4
    for (_, end, _, _), (start, _, _, _) in zip(steps, steps[1:]):
        assert start >= end
    for start, end, name, _ in spans:
        assert sum(1 for s0, s1, _, _ in steps
                   if s0 <= start and end <= s1) == 1, name
    assert not [n for _, _, n, _ in spans if "alpha" in n or "beta" in n]
    chunks = [st for _, _, n, st in spans
              if n == "serving::prefill_dispatch"]
    assert [(st["request_id"], st["start"], st["tokens"]) for st in chunks] \
        == [("alpha", 0, 4), ("alpha", 4, 2), ("beta", 0, 3)]
    firsts = [st["request_id"] for _, _, n, st in spans
              if n == "serving::first_token"]
    assert firsts == ["alpha", "beta"]
    assert {st["slots"] for _, _, n, st in spans
            if n == "serving::decode_dispatch"} <= {1, 2}


@pytest.mark.parametrize("mode", ["sampled", "speculative"])
def test_sampled_and_speculative_iterations_use_the_same_names(
        model, tmp_path, mode):
    kw, submit_kw = {}, {}
    if mode == "sampled":
        submit_kw = dict(temperature=0.8, seed=3)
    else:
        paddle.seed(1)
        draft = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=1))
        draft.eval()
        kw = dict(speculative=draft)
    eng = _engine(model, **kw)
    eng.generate([_prompt(5)], max_new_tokens=2, **submit_kw)
    spans = _traced(eng, tmp_path, lambda: eng.submit(
        _prompt(6, 1), max_new_tokens=4, **submit_kw))
    assert {name for _, _, name, _ in spans} == SPANS | {"serving::step"}
    spans = [sp for sp in spans if sp[2] != "serving::step"]
    for (_, end, name, _), (start, _, nxt, _) in zip(spans, spans[1:]):
        assert start >= end, (name, nxt)


def test_record_event_buffers_only_inside_a_session():
    drained = profiler._recorder.drain()              # whatever was left
    for _ in range(100):
        with profiler.RecordEvent("hot_path", request_id="r1"):
            pass
    assert profiler._recorder.drain() == []
    with profiler.Profiler() as prof:
        with profiler.RecordEvent("in_session"):
            pass
    assert [name for _, name, *_ in prof.events] == ["in_session"]
    del drained


def test_record_event_lands_in_a_jax_trace_without_a_session(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with profiler.RecordEvent("serving::user_range", slots=4):
            pass
    finally:
        jax.profiler.stop_trace()
    (span,) = _host_spans(str(tmp_path))
    assert span[2] == "serving::user_range" and span[3] == {"slots": 4}


def _module_and_scopes(lowered):
    text = lowered.as_text(debug_info=True)
    (module,) = set(re.findall(r"module @(\w+)", text))
    parts = {p for name in re.findall(r'loc\("([^"]+)"', text)
             for p in name.split("/")}
    return module, parts


def test_every_step_program_and_model_part_has_a_name(model):
    eng = _engine(model)
    S = eng.config.max_batch_size
    decode = eng._decode_step.__wrapped__.lower(
        np.zeros((S, 1), np.int32), eng.pool.layers, eng._block_tables,
        eng._lengths)
    module, parts = _module_and_scopes(decode)
    assert module == "jit_paged_decode_step"
    assert parts >= set(MODEL_SCOPES)
    prefill = eng._prefill_step.__wrapped__.lower(
        np.zeros((1, eng.chunk_tokens), np.int32), eng.pool.layers,
        eng._block_tables[:1], np.zeros((1,), np.int32), np.int32(2))
    module, parts = _module_and_scopes(prefill)
    assert module == "jit_chunked_prefill_step"
    assert parts >= set(MODEL_SCOPES)
    assert eng._sampled_decode_step.__name__ == "sampled_decode_step"


def test_a_to_static_program_takes_its_function_s_name_and_the_backward_its_scope():
    paddle.seed(0)
    lm = LlamaForCausalLM(LlamaConfig.tiny(fused_lm_loss=True))
    optimizer = paddle.optimizer.AdamW(1e-3, parameters=lm.parameters())

    def train_step(tokens):
        loss = lm(tokens, labels=tokens)[0]
        loss.backward()
        optimizer.step()
        optimizer.clear_grad()
        return loss

    step = paddle.jit.to_static(train_step)
    tokens = paddle.to_tensor(np.arange(16, dtype=np.int32).reshape(2, 8))
    step(tokens)
    step(tokens)
    programs = step.compiled_programs()
    assert programs
    for program in programs:
        text = program.as_text()
        assert "HloModule jit_train_step" in text
        names = set(re.findall(r'op_name="([^"]+)"', text))
        assert any("/optimizer_step/" in n for n in names)
        assert any("/lm_loss/" in n for n in names)
        # the tape re-enters the forward's scope for the backward
        assert any(re.search(r"/mlp/transpose\(", n) for n in names)
        assert any(re.search(r"/attn_qkv/transpose\(", n) for n in names)


# ------------------------------------------------- the step's account
def _warm_engine(model, **kw):
    """An engine whose usual step is known: nothing is slow before
    ``_USUAL_STEPS`` steps have been seen."""
    eng = _engine(model, **kw)
    while eng.metrics._usual_seen < serving_metrics._USUAL_STEPS:
        eng.generate([_prompt(5, 1), _prompt(7, 2)], max_new_tokens=24)
    assert eng.metrics.slow_step_log.maxlen == serving_metrics.SLOW_STEP_LOG
    return eng


def _hold(seconds, how):
    """Spend ``seconds`` asleep, or as many of the thread's CPU."""
    if how == "sleep":
        time.sleep(seconds)
        return
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


@pytest.mark.parametrize("how", ["sleep", "busy"])
@pytest.mark.parametrize("where", ["decode_prepare", "sample_emit"])
def test_a_sleep_in_host_code_is_off_the_cpu_and_a_busy_loop_is_not(
        model, monkeypatch, where, how):
    eng = _warm_engine(model)
    held = 0.1                  # against a usual step of a millisecond
    planted = []

    def plant(*_):
        if not planted and eng.metrics.decode_iterations > at:
            planted.append(eng.metrics.engine_steps)
            _hold(held, how)

    if where == "decode_prepare":
        ensure = eng._ensure_blocks
        monkeypatch.setattr(eng, "_ensure_blocks",
                            lambda *a: (plant(), ensure(*a))[1])
    before = eng.metrics.as_dict()
    at = eng.metrics.decode_iterations + 2
    eng.submit(_prompt(6, 3), max_new_tokens=8,
               on_token=plant if where == "sample_emit" else None)
    eng.run_until_complete()
    after = eng.stats()
    rose = {k: after["counters"][k] - before["counters"][k]
            for k in STEP_COUNTERS}
    (record,) = [r for r in after["slow_steps"] if r["step"] == planted[0]]
    assert set(record) == RECORD_FIELDS
    assert tuple(record["phase_ns"]) == PHASES
    assert rose["slow_steps"] >= 1
    assert rose["slow_step_excess_ns"] >= 0.9 * held * 1e9
    assert record["wall_ns"] >= held * 1e9 > 3 * record["usual_ns"]
    assert max(record["phase_ns"], key=record["phase_ns"].get) == where
    assert record["phase_ns"][where] >= held * 1e9
    assert record["slots"] == 1 and record["chunks"] == 0
    assert record["programs_behind"] == 1 and record["programs_compiled"] == 0
    assert sum(record["phase_ns"].values()) + record["unowned_ns"] \
        == record["wall_ns"]
    # the thread's CPU clock is read at the end of a slow step (and of
    # every few others): the record holds its CPU time since the reading
    # before, over that stretch of wall time and those steps, this one
    # the last
    every = serving_metrics.THREAD_READ_EVERY
    assert 1 <= record["cpu_over_steps"] <= every
    assert record["cpu_over_ns"] >= record["wall_ns"]
    if how == "sleep":
        # asleep: wall time passed and no CPU time beyond the few usual
        # steps' did
        assert record["cpu_ns"] < 0.5 * held * 1e9
        assert rose["step_cpu_ns"] < 0.5 * held * 1e9
        assert record["nvcsw"] >= 1          # it gave the CPU up itself
    else:
        assert record["cpu_ns"] >= held * 1e9
        assert rose["step_cpu_ns"] >= held * 1e9
    assert rose["step_wall_ns"] >= record["wall_ns"]
    assert rose["step_cpu_ns"] >= record["cpu_ns"]


def test_an_injected_step_delay_lands_where_the_watchdog_sits(model):
    """``FaultPlan(step_delay_s=...)`` sleeps at the head of a step
    attempt, inside the watchdog's window: the chunk's watchdog runs
    inside ``prefill_dispatch``, the decode's around its two phases, so
    between ``decode_prepare`` and ``decode_dispatch``, which no phase
    owns."""
    eng = _warm_engine(model)
    with FaultPlan(step_delay_s={1: 0.05, 2: 0.07}) as plan:
        step = eng.metrics.engine_steps
        eng.submit(_prompt(3, 5), max_new_tokens=3)
        eng.run_until_complete()
    assert [kind for kind, *_ in plan.injected] == ["serving_delay"] * 2
    (record,) = [r for r in eng.stats()["slow_steps"] if r["step"] == step]
    assert record["chunks"] == 1 and record["slots"] == 1
    assert 0.05e9 <= record["phase_ns"]["prefill_dispatch"] < 0.07e9
    assert record["cpu_ns"] < 0.2 * record["cpu_over_ns"]
    assert record["unowned_ns"] >= 0.07e9
    assert record["phase_ns"]["decode_dispatch"] < 0.02e9


class _Program:
    """What the account reads of a step program."""
    calls = compiles = 0


@pytest.fixture
def hand_clock(monkeypatch):
    """The account's wall clock, moved by hand: a step takes what the
    test says it took."""
    at = [10**9]
    monkeypatch.setattr(serving_metrics, "_now_ns", lambda: at[0])
    return at


def _synthetic_step(metrics, clock, ms, program=None, compiles=0):
    with metrics.step():
        if program is not None:
            program.calls += 1
            program.compiles += compiles
        metrics.on_decode_iteration(1, 2, 0.0)
        clock[0] += int(ms * 1e6)


def test_the_log_of_slow_steps_never_holds_more_than_64(hand_clock):
    metrics = serving_metrics.ServingMetrics()
    for _ in range(serving_metrics._USUAL_STEPS):
        _synthetic_step(metrics, hand_clock, 25)
    assert metrics._usual_ns == 25e6 and not metrics.slow_steps
    for i in range(80):
        _synthetic_step(metrics, hand_clock, 125 + i)
        for _ in range(4):
            _synthetic_step(metrics, hand_clock, 25)
        assert len(metrics.as_dict()["slow_steps"]) == min(i + 1, 64)
    assert metrics.slow_steps == 80
    log = metrics.as_dict()["slow_steps"]
    assert [r["wall_ns"] for r in log] \
        == [(125 + i) * 10**6 for i in range(16, 80)]
    assert [r["step"] for r in log] \
        == [serving_metrics._USUAL_STEPS + 5 * i for i in range(16, 80)]
    # (a stall every fifth step would double the mean in the end: each
    # enters it as three usual steps)
    assert all(25e6 <= r["usual_ns"] < 50e6 for r in log)
    assert metrics.slow_step_excess_ns >= 80 * (125 - 50) * 10**6
    # a copy: the caller's edits do not reach the log
    log[0]["wall_ns"] = 0
    assert metrics.slow_step_log[0]["wall_ns"] > 0


def test_short_dispatch_only_steps_do_not_make_the_next_ones_slow(
        hand_clock):
    """Nine chunk-only iterations a tenth as long as the rest (the reask
    cell runs them ahead of a first-token read) weigh 1/64 each: the
    mean falls by an eighth, where a weight of 0.2 would take it to a
    quarter and make every ordinary step after them slow."""
    metrics = serving_metrics.ServingMetrics()
    for _ in range(serving_metrics._USUAL_STEPS):
        _synthetic_step(metrics, hand_clock, 25)
    for _ in range(9):
        _synthetic_step(metrics, hand_clock, 2.5)
    assert 0.87 * 25e6 < metrics._usual_ns < 0.89 * 25e6
    for _ in range(12):
        _synthetic_step(metrics, hand_clock, 28.4)     # one with a chunk
    assert metrics.slow_steps == 0 and not metrics.slow_step_log
    # nothing dispatched, no one waiting: neither slow nor in the mean
    usual = metrics._usual_ns
    with metrics.step():
        hand_clock[0] += 10**9
    assert metrics.slow_steps == 0 and metrics._usual_ns == usual
    assert metrics.step_wall_ns >= 10**9
    # ... a stall of seconds is, and moves the mean by 1/32 of it
    _synthetic_step(metrics, hand_clock, 2700)
    assert metrics.slow_steps == 1
    assert metrics._usual_ns == pytest.approx(usual * (1 + 2 / 64))
    assert metrics.slow_step_excess_ns == 2700 * 10**6 - int(usual) \
        == metrics.slow_step_log[0]["wall_ns"] \
        - metrics.slow_step_log[0]["usual_ns"]


def test_a_lasting_change_of_load_is_followed_and_a_compile_is_no_sample(
        hand_clock):
    metrics = serving_metrics.ServingMetrics()
    program = _Program()
    metrics.programs = (program,)
    # nothing is slow before the usual step is known, a compile least
    _synthetic_step(metrics, hand_clock, 30000, program, compiles=1)
    for _ in range(serving_metrics._USUAL_STEPS):
        _synthetic_step(metrics, hand_clock, 25, program)
    assert metrics._usual_ns == 25e6 and not metrics.slow_steps
    # a step that compiled is slow (its record says why) and no sample
    _synthetic_step(metrics, hand_clock, 3000, program, compiles=1)
    assert metrics.slow_step_log[-1]["programs_compiled"] == 1
    assert metrics._usual_ns == 25e6
    # every step four times as long from here on: slow at first, then
    # the usual step
    slow = []
    for _ in range(40):
        _synthetic_step(metrics, hand_clock, 100, program)
        slow.append(metrics.slow_steps)
    assert 5 <= slow[-1] - 1 <= 15 and slow[-1] == slow[-20]
    assert metrics.as_dict()["counters"]["programs_dispatched"] \
        == serving_metrics._USUAL_STEPS + 42


def test_three_thousand_requests_leave_a_bounded_number_of_timelines(model):
    eng = _engine(model)
    bound = serving_metrics.FINISHED_REQUESTS
    sent = 0
    while sent < 3000 or eng.has_work():
        while sent < 3000 and len(eng.scheduler.waiting) < 4:
            eng.submit(_prompt(3, sent % 7), max_new_tokens=1)
            sent += 1
        eng.step()
        live = len(eng.metrics.requests)
        assert live <= 2 + 4 + 1
        if sent % 500 == 0:
            assert len(eng.metrics.as_dict()["requests"]) <= bound + live
    assert not eng.metrics.requests
    requests = eng.stats()["requests"]
    assert len(requests) == bound == len(eng.metrics.finished)
    assert list(requests)[-1] == "req-2999"
    assert all(r["finish_reason"] == "length" and r["ttft_s"] is not None
               for r in requests.values())
    assert eng.metrics.completed == 3000


def test_a_platform_that_counts_nothing_by_thread_reads_zeros(
        hand_clock, monkeypatch):
    """No ``RUSAGE_THREAD``, or one whose every count stays 0 (a
    sandboxed kernel): the account goes on without the system call."""
    monkeypatch.setattr(serving_metrics, "_thread_usage",
                        lambda: serving_metrics._NO_USAGE)
    metrics = serving_metrics.ServingMetrics()
    for _ in range(serving_metrics._USUAL_STEPS):
        _synthetic_step(metrics, hand_clock, 25)
    _synthetic_step(metrics, hand_clock, 250)
    (record,) = metrics.as_dict()["slow_steps"]
    assert [record[k] for k in ("nvcsw", "nivcsw", "minflt", "majflt")] \
        == [0, 0, 0, 0]
    counters = metrics.as_dict()["counters"]
    assert counters["step_nivcsw"] == counters["step_minflt"] == 0
    assert counters["slow_step_excess_ns"] == 225 * 10**6
    assert set(record) == RECORD_FIELDS
