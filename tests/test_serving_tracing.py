"""Spans and counters inside ``Engine.step()``, the one span primitive
(``profiler.RecordEvent``), and the names the step programs and the
model's parts carry into a trace (ISSUE 26)."""
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
from paddle_tpu.serving import Engine, ServingConfig
from paddle_tpu.serving.metrics import PHASES

NEW_COUNTERS = (
    "engine_steps", "prefill_steps", "prefill_chunks_run",
    "prefill_context_tokens", "decode_context_tokens", "prompt_tokens", "cached_prompt_tokens",
    "admissions", "queue_wait_ns", "lane_wait_ns")
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
    counters = _engine(model).metrics.as_dict()["counters"]
    names = NEW_COUNTERS + tuple(f"step_ns.{p}" for p in PHASES)
    assert len(PHASES) == 8
    for name in names:
        assert counters[name] == 0 and isinstance(counters[name], int), name
    # a window's change can be taken of every key from the start
    assert all(isinstance(v, (int, float)) for v in counters.values())


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
    steps = 0
    while eng.has_work():
        eng.step()
        steps += 1
    c = eng.metrics.as_dict()["counters"]
    assert steps == c["engine_steps"] == 4
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
    timeline = eng.metrics.requests["req-0"]
    assert timeline.submitted_ns <= timeline.first_admitted_ns \
        <= timeline.first_chunk_ns <= timeline.first_token_ns


def test_the_phases_cover_the_step(model):
    """What no phase owns is the watchdogs' bookkeeping between the
    spans, some 60 us a step whatever the model: a tenth of this toy's
    0.6 ms step, 0.03 % of a 180 ms one."""
    eng = _engine(model)
    eng.generate([_prompt(5)], max_new_tokens=2)      # compiles
    before = sum(eng.metrics.step_ns.values())
    for n in (6, 9, 3):
        eng.submit(_prompt(n, n), max_new_tokens=6)
    wall = 0
    while eng.has_work():
        t0 = time.perf_counter_ns()
        eng.step()
        wall += time.perf_counter_ns() - t0
    inside = sum(eng.metrics.step_ns.values()) - before
    assert 0.8 * wall <= inside <= wall


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
    names = [name for _, _, name, _ in spans]
    # the four steps worked out above: one span a phase a step it ran in
    assert {n: names.count("serving::" + n) for n in PHASES} == {
        "admit": 4, "prefill_dispatch": 3, "first_token": 2,
        "decode_prepare": 3, "decode_dispatch": 3, "decode_fetch": 3,
        "sample_emit": 3, "pool_sync": 4}
    for (_, end, name, _), (start, _, nxt, _) in zip(spans, spans[1:]):
        assert start >= end, (name, nxt)                # never nested
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
    assert {name for _, _, name, _ in spans} == SPANS
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
