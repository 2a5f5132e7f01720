"""Unit tests of the yardstick: statistics, trace reduction, FLOP count,
traffic lengths and the logit comparison."""
import json
import os

import numpy as np
import pytest

from benchmarks.harness import cells, flops, models, stats, trace_reduce

BENCH_DIR = os.path.join(cells.REPO_ROOT, "benchmarks")


def test_percentile_interpolates_between_order_statistics():
    xs = [4.0, 1.0, 3.0, 2.0]
    assert stats.percentile(xs, 0) == 1.0
    assert stats.percentile(xs, 100) == 4.0
    assert stats.percentile(xs, 50) == 2.5
    assert stats.percentile(list(range(101)), 95) == 95
    assert stats.percentile([], 95) is None
    assert stats.percentile([7.0], 95) == 7.0


def test_union_seconds_merges_overlaps():
    assert stats.union_seconds([(0, 2), (1, 3), (5, 6), (5.5, 5.8)]) == 4
    assert stats.union_seconds([]) == 0


DEV, OPS, HOST, PY = "/device:TPU:0", "XLA Ops", "/host:CPU", "python"


def _hand_built_trace():
    us = 1000
    return [
        # host: three flat spans covering 0..100 us; the first 20 us are
        # the profiler's start-up, before its device line records
        (HOST, PY, "bench.submit", 0, 10 * us),
        (HOST, PY, "bench.engine_step", 10 * us, 70 * us),
        (HOST, PY, "bench.bookkeeping", 80 * us, 20 * us),
        # device: overlapping ops 20..50, a kernel 60..70, one op 90..95
        (DEV, OPS, "fusion", 20 * us, 20 * us),
        (DEV, OPS, "fusion", 30 * us, 20 * us),
        (DEV, OPS, "pallas:fused_paged_decode", 60 * us, 10 * us),
        (DEV, OPS, "copy.7", 90 * us, 5 * us),
        # outside the spans' window: clipped away
        (DEV, OPS, "fusion.3", 200 * us, 10 * us),
        # a whole-program event on another line must not count
        (DEV, "XLA Modules", "jit_step", 0, 100 * us),
        # host-side noise
        (HOST, PY, "PjitFunction(step)", 12 * us, 5 * us),
    ]


def test_reduce_trace_on_hand_built_intervals():
    r = trace_reduce.reduce_trace(_hand_built_trace())
    # the slice starts at the first device operation (20 us), not at the
    # first span: what the profiler missed while it started is not idle
    assert r["window_s"] == pytest.approx(80e-6)
    assert r["busy_s"] == pytest.approx(45e-6)      # 30 + 10 + 5
    assert r["pallas_s"] == pytest.approx(10e-6)
    ops = dict(r["device_ops"])
    assert ops["fusion"] == pytest.approx(40e-6)    # summed, not unioned
    assert ops["pallas:fused_paged_decode"] == pytest.approx(10e-6)
    gaps = dict(r["idle_gaps"])
    # idle: 50..60 and 70..80 (step), 80..90 and 95..100 (bookkeeping)
    assert "bench.submit" not in gaps
    assert gaps["bench.engine_step"] == pytest.approx(20e-6)
    assert gaps["bench.bookkeeping"] == pytest.approx(15e-6)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert trace_reduce.idle_pct({"trace": r}) == pytest.approx(43.75)


def test_reduce_trace_starts_at_the_first_span_when_the_device_ran_before():
    us = 1000
    r = trace_reduce.reduce_trace([
        (DEV, OPS, "fusion.1", 0, 30 * us),          # running already
        (HOST, PY, "bench.train_step", 10 * us, 90 * us),
        (DEV, OPS, "fusion.2", 50 * us, 10 * us)])
    assert r["window_s"] == pytest.approx(90e-6)    # 10..100
    assert r["busy_s"] == pytest.approx(30e-6)      # 10..30 and 50..60
    assert dict(r["idle_gaps"]) == {
        "bench.train_step": pytest.approx(60e-6)}


def test_an_idle_gap_goes_to_the_program_s_span_where_the_host_was_in_one():
    """``breakdown.idle_gaps`` names the innermost span: the program's
    ``<part>::<phase>`` inside ``bench.engine_step``, the benchmark's own
    where the program had none open; the slice and the busy time do not
    move."""
    us = 1000
    plain = _hand_built_trace()
    spans = [(HOST, PY, "serving::decode_dispatch", 12 * us, 10 * us),
             (HOST, PY, "serving::decode_fetch", 22 * us, 33 * us),
             (HOST, PY, "serving::sample_emit", 72 * us, 6 * us)]
    r = trace_reduce.reduce_trace(plain + spans)
    bare = trace_reduce.reduce_trace(plain)
    assert (r["window_s"], r["busy_s"], r["device_ops"]) \
        == (bare["window_s"], bare["busy_s"], bare["device_ops"])
    gaps = dict(r["idle_gaps"])
    # 50..60: fetch until 55, then nobody's; 70..80: sample_emit 72..78
    assert gaps["serving::decode_fetch"] == pytest.approx(5e-6)
    assert gaps["serving::sample_emit"] == pytest.approx(6e-6)
    assert gaps["bench.engine_step"] == pytest.approx(9e-6)
    assert gaps["bench.bookkeeping"] == pytest.approx(15e-6)
    assert "serving::decode_dispatch" not in gaps
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_reduce_trace_without_device_ops_is_none():
    assert trace_reduce.reduce_trace(
        [(HOST, PY, "bench.engine_step", 0, 1000)]) is None


def test_reduce_trace_averages_over_chips_and_charges_unowned_gaps():
    events = [(f"/device:TPU:{i}", OPS, "fusion.1", 0, 1000 * (i + 1))
              for i in range(2)] + [(DEV, OPS, "fusion.9", 3000, 1000)]
    r = trace_reduce.reduce_trace(events)
    assert r["devices"] == 2
    assert r["window_s"] == pytest.approx(4e-6)     # the ops' own range
    assert r["busy_s"] == pytest.approx((2e-6 + 2e-6) / 2)
    assert dict(r["idle_gaps"]) == {"bench.outside": pytest.approx(2e-6)}


def test_model_flops_per_token_against_a_hand_count():
    cfg = {"hidden_size": 64, "intermediate_size": 128,
           "num_hidden_layers": 2, "num_attention_heads": 4,
           "num_key_value_heads": 2, "vocab_size": 256}   # LlamaConfig.tiny
    # a layer: q 64x64, k and v 64x32 each, o 64x64, three 64x128 MLP
    layer = 64 * 64 + 2 * 64 * 32 + 64 * 64 + 3 * 64 * 128
    assert layer == 36864
    params = 2 * layer + 64 * 256
    assert flops.matmul_params(cfg) == params == 90112
    # attention at seq 32: 6 * 32 * (4 heads * 16) a layer
    assert flops.model_flops_per_token(cfg, 32) == \
        6 * params + 2 * 6 * 32 * 64 == 565248


def test_flops_at_the_published_widths():
    cfg = cells.load_json(os.path.join(
        BENCH_DIR, "configs", "mistral-7b-v0.3-train.json"))
    per_layer = (flops.matmul_params(cfg) - 4096 * 32768) \
        / cfg["num_hidden_layers"]
    assert per_layer == 218103808                   # 218.1 M, as ISSUE 25


def test_compare_logits_verdict():
    want = np.array([[1.0, -2.0, 0.5], [0.1, 0.2, -4.0]], np.float32)
    ok = models.compare_logits(want + 0.05, want)   # tolerance 4 * 2^-5
    assert ok["ok"] and ok["tolerance"] == pytest.approx(0.125)
    assert not models.compare_logits(want + 0.2, want)["ok"]
    bad = want.copy()
    bad[0, 0] = np.nan
    assert not models.compare_logits(bad, want)["ok"]


@pytest.fixture(scope="module")
def tiny_model_and_reference():
    config = dict(cells.load_json(os.path.join(
        BENCH_DIR, "configs", "mistral-7b-v0.3-train.json")),
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128)
    config["model_config_kwargs"] = dict(config["model_config_kwargs"],
                                         dtype="float32")
    del config["head_dim"]
    model = models.build_model(config, seed=5)
    return config, model, models.load_reference(config)


def test_forward_logits_agree_with_the_reference_position_by_position(
        tiny_model_and_reference):
    config, model, reference = tiny_model_and_reference
    row = np.random.default_rng(1).integers(0, 256, size=48, dtype=np.int32)
    got = models.forward_logits(model, row, last=32)
    weights = reference.weights_of(model)
    want = np.asarray(reference.logits(weights, config, row, last=32))
    assert got.shape == want.shape == (32, 256)
    assert models.compare_logits(got, want, tol=1e-4)["ok"]


@pytest.mark.parametrize("fault", ["a layer skipped", "weights in 8 bits"])
def test_the_training_checks_fail_for_wrong_or_coarser_mathematics(
        tiny_model_and_reference, fault):
    """Both of the training cell's comparisons, at their committed
    tolerances, against a model that skips a layer the reference has or
    multiplies by weights rounded to 8 bits (float8 e4m3)."""
    import jax.numpy as jnp

    config, model, reference = tiny_model_and_reference
    batch = np.random.default_rng(2).integers(0, 256, size=(2, 48),
                                              dtype=np.int32)
    if fault == "a layer skipped":
        faulty = model
    else:
        faulty = models.build_model(config, seed=5)
        for p in faulty.parameters():
            p._value = p._value.astype(jnp.float8_e4m3fn).astype(
                p._value.dtype)
    got = models.forward_logits(faulty, batch[0], last=32)
    loss = float(np.asarray(faulty(jnp.asarray(batch),
                                   labels=jnp.asarray(batch))[0]._value))
    # (the compiled forward re-binds the model's arrays: read them after)
    truth = reference.weights_of(model)
    if fault == "a layer skipped":
        truth = dict(truth, layers=truth["layers"] + [truth["layers"][0]])
    want = np.asarray(reference.logits(truth, config, batch[0], last=32))
    assert not models.compare_logits(got, want)["ok"]
    want_loss = reference.causal_lm_loss(truth, config, batch)
    assert abs(loss - want_loss) / want_loss > models.LOSS_TOL


def test_every_seed_is_dealt_the_same_balanced_rounds_in_the_same_order():
    kind = cells.load_module(os.path.join(
        BENCH_DIR, "kinds", "closed_loop_serve.py"), "closed_loop_serve")
    for name in sorted(os.listdir(os.path.join(BENCH_DIR, "traffic"))):
        mix = cells.load_json(os.path.join(BENCH_DIR, "traffic", name))
        if mix["kind"] != "closed_loop_serve":
            continue
        rounds = kind.length_rounds(mix)
        assert rounds == kind.length_rounds(json.loads(json.dumps(mix)))
        assert [len(r) for r in rounds] == \
            [mix["round"]] * (mix["pool"] // mix["round"])
        prompts = sorted(p for r in rounds for p, _ in r)
        assert prompts[0] >= mix["prompt_tokens"]["min"]
        assert prompts[-1] <= mix["prompt_tokens"]["max"]
        assert abs(prompts[len(prompts) // 2]
                   - mix["prompt_tokens"]["median"]) \
            <= 0.1 * mix["prompt_tokens"]["median"]
        # every round asks for about the same work
        totals = [sum(p for p, _ in r) for r in rounds]
        assert max(totals) <= 1.10 * min(totals)
        whole = sorted(pair for r in rounds for pair in r)
        dealt = []
        for seed in (0, 2**31 + 5):
            loop = kind.ClosedLoop(None, mix, 100, seed)
            loop.first = [False] * mix["clients"]
            dealt.append([loop._next_lengths(0)
                          for _ in range(2 * mix["pool"])])
        assert dealt[0] == dealt[1]                 # whatever the seed
        for d in dealt:                                      # the same set
            assert sorted(d[:mix["pool"]]) == whole
            assert sorted(d[mix["pool"]:]) == whole
            assert sorted(d[:mix["round"]]) in [sorted(r) for r in rounds]


def test_short_name_of_a_device_event():
    kernel = ('%fused_paged_decode.17 = (f32[32,4,8,4,128]{4,3,2,1,0}) '
              'custom-call(s32[32,256]{1,0} %args_2_.1), '
              'custom_call_target="tpu_custom_call", frontend_attributes={}')
    assert trace_reduce.short_name(kernel) == "pallas:fused_paged_decode"
    assert trace_reduce.is_pallas(trace_reduce.short_name(kernel))
    copy = "%copy.746 = bf16[2048,16,8,128]{3,2,1,0} copy(bf16[2048] %p)"
    assert trace_reduce.short_name(copy) == "copy"
    assert not trace_reduce.is_pallas("copy")
    assert trace_reduce.short_name("fusion.3") == "fusion"
