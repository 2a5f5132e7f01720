"""A model that generates by diffusion over blocks, served by the normal
engine (models/sdar_moe.py, models/generation.py::make_paged_block_step,
serving/engine.py::_block_iteration), at tiny widths on the CPU with
seeded random weights, against the plain reference
(``benchmarks/reference/sdar_moe.py``, the one the benchmark's ``correct``
uses)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.kernels import moe_experts
from paddle_tpu.kernels.chunked_prefill import fused_chunked_attention
from paddle_tpu.kernels.fusion import force_pallas_interpret
from paddle_tpu.kernels.paged_attention import paged_context_partials
from paddle_tpu.models import DroplessMoE, SDARMoEConfig, SDARMoEForCausalLM
from paddle_tpu.models.generation import (make_chunked_prefill_step,
                                          make_paged_block_step,
                                          unmask_schedule, unmask_select)
from paddle_tpu.models.sdar_moe import routing_witness
from paddle_tpu.serving import Engine, ServingConfig
from benchmarks.reference import sdar_moe as reference


def _model(seed=0, **overrides):
    paddle.seed(seed)
    model = SDARMoEForCausalLM(SDARMoEConfig.tiny(**overrides))
    model.eval()
    return model


def _cfg(model):
    """The model's settings as a configuration file gives them."""
    c = model.config
    return {k: getattr(c, k) for k in (
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "rms_norm_eps", "rope_theta", "num_experts", "num_experts_per_tok",
        "norm_topk_prob", "block_length")}


def _engine(model, **kw):
    kw = dict(dict(max_batch_size=3, block_size=8, num_blocks=48,
                   chunk_tokens=16), **kw)
    return Engine(model, ServingConfig(**kw))


# ------------------------------------------------- the step programs
@pytest.mark.parametrize("L,T", [(4, 1), (4, 2), (4, 4), (8, 1), (8, 2),
                                 (8, 8)])
def test_chunk_and_block_programs_against_the_reference(L, T):
    """A prompt that is not a multiple of L through the engine's chunk
    program, then blocks through its block program, teacher-forced: at
    every denoise step and across every commit the program's logits
    are the reference's full forward of the same row, under the witness
    of what those programs chose."""
    model = _model(block_length=L, denoising_steps=T,
                   remasking="low_confidence_static")
    eng = _engine(model)
    gen, cfg = model.config, _cfg(model)
    chunk = make_chunked_prefill_step(model, fused=eng.config.fused_kernels)
    block = make_paged_block_step(model, fused=eng.config.fused_kernels)
    weights = reference.weights_of(model)
    S, C = eng.config.max_batch_size, eng.chunk_tokens
    rng = np.random.default_rng(L * 100 + T)
    n_prompt = 5 * L + L // 2 + 1
    prompt = rng.integers(1, 250, size=n_prompt, dtype=np.int32)
    whole = n_prompt // L * L
    table = np.zeros((S, eng.max_blocks_per_seq), np.int32)
    table[1, :8] = np.arange(1, 9)          # slot 1: slot 0 stays idle
    pools = eng.pool.layers
    for at in range(0, whole, C):
        n = min(C, whole - at)
        ids = np.zeros((1, C), np.int32)
        ids[0, :n] = prompt[at:at + n]
        stats, pools = chunk(ids, pools, table[1:2],
                             np.asarray([at], np.int32), np.int32(n - 1))
        assert np.asarray(stats)[1] == n * gen.num_experts_per_tok \
            * gen.num_hidden_layers
    eng.pool.layers = [tuple(e) for e in pools]

    ids = np.zeros((S, L), np.int32)
    masked = np.zeros((S, L), bool)
    start, mode = np.zeros((S,), np.int32), np.zeros((S,), np.int32)
    n_unmask = np.zeros((S,), np.int32)
    tau = np.full((S,), 2.0, np.float32)
    committed, known = list(prompt[:whole]), list(prompt[whole:])
    schedule = unmask_schedule(L, T)
    compared = 0
    for b in range(3):
        ids[1], masked[1] = gen.mask_token_id, True
        ids[1, :len(known)], masked[1, :len(known)] = known, False
        known = []
        for step in range(T):
            if not masked[1].any():
                break
            n = L if step == T - 1 else schedule[step]
            mode[1], n_unmask[1], start[1] = 1, n, len(committed)
            was = masked[1].copy()
            small, probe, pools = block(ids, masked, start, mode, n_unmask,
                                        tau, eng.pool.layers, table)
            eng.pool.layers = [tuple(e) for e in pools]
            small = np.asarray(small)
            row = np.asarray(committed + list(ids[1]), np.int32)
            chose = routing_witness(
                model, eng, row, table[1], len(committed),
                in_flight=np.asarray(probe["chosen"])[:, 1])
            want, report = reference.logits(weights, cfg, row, L,
                                            witness=chose)
            want = np.asarray(want)
            assert report["ok"] and report["not_first_choice"] == 0, \
                (b, step, report)
            new_ids = small[:S * L].reshape(S, L)[1]
            now = small[S * L:2 * S * L].reshape(S, L)[1] != 0
            took, cand, _ = reference.remask(want, was, n)
            assert (was & ~now == took).all()
            assert (new_ids[took] == cand[took]).all()
            assert (new_ids[~took] == ids[1][~took]).all()
            fed = rng.integers(1, 250, size=L, dtype=np.int32)
            ids[1] = np.where(took, fed, ids[1])
            masked[1] = now
            compared += 1
        assert not masked[1].any()
        mode[1], n_unmask[1], start[1] = 2, 0, len(committed)
        small, _, pools = block(ids, masked, start, mode, n_unmask, tau,
                                eng.pool.layers, table)
        np.asarray(small)       # the step has read its inputs
        eng.pool.layers = [tuple(e) for e in pools]
        committed += list(ids[1])
    assert compared >= 3
    # what the commits wrote is what a full forward computes: the pool's
    # witness of every committed position is the reference's own choice
    final = routing_witness(model, eng, np.asarray(committed), table[1],
                            len(committed))
    _, report = reference.logits(weights, cfg, np.asarray(committed), L,
                                 witness=final)
    assert report["ok"] and report["not_first_choice"] == 0
    assert eng.decode_cache_size() <= 1 and eng.prefill_cache_size() <= 1


def test_block_program_logits_of_the_probe_slot():
    """Slot 0's logits (the block program's probe output) against the
    reference at a denoise step, and again after a commit."""
    L = 4
    model = _model(block_length=L, denoising_steps=2,
                   remasking="low_confidence_static")
    eng = _engine(model)
    cfg, gen = _cfg(model), model.config
    block = make_paged_block_step(model, fused=eng.config.fused_kernels)
    weights = reference.weights_of(model)
    S = eng.config.max_batch_size
    rng = np.random.default_rng(5)
    table = np.zeros((S, eng.max_blocks_per_seq), np.int32)
    table[0, :4] = np.arange(1, 5)
    ids = np.zeros((S, L), np.int32)
    masked = np.zeros((S, L), bool)
    zeros = np.zeros((S,), np.int32)
    tau = np.full((S,), 2.0, np.float32)
    committed = []
    for b in range(3):
        start, mode, n_unmask = zeros.copy(), zeros.copy(), zeros.copy()
        ids[0] = rng.integers(1, 250, size=L)
        ids[0, 2:] = gen.mask_token_id
        masked[0] = [False, False, True, True]
        start[0], mode[0], n_unmask[0] = len(committed), 1, 1
        _, probe, pools = block(ids, masked, start, mode, n_unmask, tau,
                                eng.pool.layers, table)
        eng.pool.layers = [tuple(e) for e in pools]
        got = np.asarray(probe["logits"])
        row = np.asarray(committed + list(ids[0]), np.int32)
        want = np.asarray(reference.logits(weights, cfg, row, L))
        assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()
        ids[0] = rng.integers(1, 250, size=L)
        masked[0] = False
        mode[0] = 2
        small, _, pools = block(ids, masked, start, mode, n_unmask, tau,
                                eng.pool.layers, table)
        np.asarray(small)       # the step has read its inputs
        eng.pool.layers = [tuple(e) for e in pools]
        committed += list(ids[0])


@pytest.mark.parametrize("seed", range(4))
def test_both_remasking_rules_against_the_reference(seed):
    rng = np.random.default_rng(seed)
    S, L, V = 5, 8, 64
    logits = rng.normal(size=(S, L, V)).astype(np.float32) * 3
    masked = rng.random((S, L)) < 0.7
    ids = rng.integers(0, V, size=(S, L)).astype(np.int32)
    n = rng.integers(0, L + 1, size=S).astype(np.int32)
    for tau in (None, 0.3):
        taus = np.full((S,), 2.0 if tau is None else tau, np.float32)
        new_ids, new_masked = unmask_select(
            jnp.asarray(logits), jnp.asarray(ids), jnp.asarray(masked),
            jnp.asarray(n), jnp.asarray(taus))
        for s in range(S):
            took, cand, conf = reference.remask(logits[s], masked[s],
                                                int(n[s]), tau)
            assert (np.asarray(new_masked)[s] == (masked[s] & ~took)).all()
            assert (np.asarray(new_ids)[s]
                    == np.where(took, cand, ids[s])).all()
            if tau is not None:
                assert (took[masked[s] & (conf > tau)]).all()


# ----------------------------------------------------------- the engine
def _plain_generation(model, prompt, n_new):
    """The family's loop, written plainly on the reference: one full
    forward a denoise step, no cache."""
    gen, cfg = model.config, _cfg(model)
    L, T = gen.block_length, gen.denoising_steps
    weights = reference.weights_of(model)
    tau = None if gen.remasking == "low_confidence_static" \
        else gen.confidence_threshold
    schedule = unmask_schedule(L, T)
    row = list(prompt)
    start = len(prompt) // L * L
    out = []
    while len(out) < n_new:
        block = row[start:] + [gen.mask_token_id] * (L - len(row[start:]))
        masked = np.arange(L) >= len(row) - start
        for step in range(T):
            if not masked.any():
                break
            lg = reference.logits(weights, cfg,
                                  np.asarray(row[:start] + block), L)
            n = L if step == T - 1 else schedule[step]
            took, cand, _ = reference.remask(np.asarray(lg), masked, n, tau)
            block = [int(cand[i]) if took[i] else block[i]
                     for i in range(L)]
            masked = masked & ~took
        out += block[len(row) - start:]
        row = row[:start] + block
        start += L
    return out[:n_new]


@pytest.mark.parametrize("L,T,rule", [
    (4, 2, "low_confidence_static"), (4, 4, "low_confidence_static"),
    (8, 1, "low_confidence_static"), (8, 3, "low_confidence_dynamic"),
    (4, 4, "low_confidence_dynamic")])
def test_engine_generates_what_the_plain_loop_does(L, T, rule):
    """Prompts whose lengths are not multiples of L (one shorter than a
    block), outputs that are not multiples of L, several slots in mixed
    states: token for token the plain loop's, delivered in order, no
    block leaked, one program a step."""
    model = _model(block_length=L, denoising_steps=T, remasking=rule,
                   confidence_threshold=0.0062)
    eng = _engine(model)
    rng = np.random.default_rng(L + T)
    sent = []
    for n_prompt, n_new in ((22, 10), (3, 7), (16, 9), (9, 5), (13, 16)):
        prompt = rng.integers(1, 250, size=n_prompt, dtype=np.int32)
        got = []
        sent.append((eng.submit(prompt, max_new_tokens=n_new,
                                on_token=got.append), got, prompt, n_new))
    while eng.step():
        pass
    for req, got, prompt, n_new in sent:
        assert req.finish_reason == "length"
        assert req.num_generated == n_new and got == req.generated
        assert got == _plain_generation(model, prompt, n_new)
    eng.pool.check_leaks()
    assert eng.decode_cache_size() == 1 and eng.prefill_cache_size() == 1
    c = eng.stats()["counters"]
    assert c["block_slot_steps"] >= c["commit_slot_steps"] > 0
    assert c["tokens_unmasked"] >= sum(n for _, _, _, n in sent)
    layers = model.config.num_hidden_layers
    runs = c["block_steps"] + c["prefill_chunks_run"]
    assert 0 < c["experts_read"] <= runs * layers * model.config.num_experts
    assert c["expert_assignments_max"] * model.config.num_experts \
        >= c["expert_assignments"]


def test_static_rule_yields_l_over_t_plus_one_tokens_a_slot_step():
    L, T = 4, 2
    model = _model(block_length=L, denoising_steps=T,
                   remasking="low_confidence_static")
    eng = _engine(model, max_batch_size=2)
    prompt = np.arange(1, 9, dtype=np.int32)
    eng.submit(prompt, max_new_tokens=8 * L)
    eng.run_until_complete()
    c = eng.stats()["counters"]
    # 8 blocks: 2 denoise steps each, a commit after all but the last
    assert c["block_slot_steps"] == 8 * T + 7
    assert c["tokens_unmasked"] == 8 * L
    assert c["commit_slot_steps"] == 7


def test_eos_ends_a_request_inside_a_block():
    model = _model(block_length=4, denoising_steps=2,
                   remasking="low_confidence_static")
    prompt = np.arange(1, 12, dtype=np.int32)
    want = _plain_generation(model, prompt, 12)
    eos = want[5]
    first = want.index(eos)
    eng = _engine(model)
    req = eng.submit(prompt, max_new_tokens=12, eos_token_id=eos)
    eng.run_until_complete()
    assert req.finish_reason == "eos" and req.generated == want[:first + 1]
    eng.pool.check_leaks()


def test_preemption_in_the_middle_of_a_block_and_resume():
    """A pool too small for both requests: the younger is preempted
    while its block is half denoised, recomputed from its tokens, and
    ends with the tokens an unpreempted run gives."""
    model = _model(block_length=4, denoising_steps=4,
                   remasking="low_confidence_static")
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 250, size=n, dtype=np.int32)
               for n in (14, 13)]
    want = [_plain_generation(model, p, 18) for p in prompts]
    eng = _engine(model, max_batch_size=2, block_size=4, num_blocks=13,
                  enable_prefix_cache=False)
    reqs = [eng.submit(p, max_new_tokens=18) for p in prompts]
    eng.run_until_complete()
    assert reqs[1].preemptions >= 1
    for req, tokens in zip(reqs, want):
        assert req.finish_reason == "length" and req.generated == tokens
    eng.pool.check_leaks()
    assert eng.decode_cache_size() == 1 and eng.prefill_cache_size() == 1
    assert eng.stats()["counters"]["preemptions"] >= 1


def test_prefix_cache_serves_a_block_model():
    model = _model(block_length=4, denoising_steps=2,
                   remasking="low_confidence_static")
    rng = np.random.default_rng(9)
    shared = rng.integers(1, 250, size=24, dtype=np.int32)
    prompts = [np.concatenate([shared, rng.integers(1, 250, size=n,
                                                    dtype=np.int32)])
               for n in (3, 6)]
    eng = _engine(model, max_batch_size=1)
    outs = []
    for p in prompts:
        req = eng.submit(p, max_new_tokens=6)
        eng.run_until_complete()
        outs.append(req.generated)
    assert eng.stats()["counters"]["prefix_cache_hits"] == 1
    assert outs == [_plain_generation(model, p, 6) for p in prompts]
    eng.pool.check_leaks()


def test_a_prompt_may_hold_the_mask_id():
    model = _model(block_length=4, denoising_steps=2,
                   remasking="low_confidence_static")
    prompt = np.full((10,), model.config.mask_token_id, np.int32)
    eng = _engine(model)
    req = eng.submit(prompt, max_new_tokens=6)
    eng.run_until_complete()
    assert req.generated == _plain_generation(model, prompt, 6)


def test_what_the_block_iteration_does_not_do_is_refused():
    model = _model()
    eng = _engine(model)
    with pytest.raises(ValueError, match="greedy only"):
        eng.submit(np.arange(1, 5), max_new_tokens=4, temperature=0.8)
    for kw in (dict(speculative=_model(seed=1)),
               dict(kv_cache_dtype="int8"), dict(block_size=6)):
        with pytest.raises(ValueError, match="block"):
            _engine(_model(), **kw)


# ------------------------------------------------------ the expert layer
def _loop_over_tokens(layer, x):
    """Every token through its experts, one at a time."""
    wr = np.asarray(layer.router._value, np.float64)
    wg, wu, wd = (np.asarray(w._value, np.float64)
                  for w in (layer.w_gate, layer.w_up, layer.w_down))
    out = np.zeros_like(x, dtype=np.float64)
    for t, row in enumerate(np.asarray(x, np.float64)):
        z = row @ wr
        p = np.exp(z - z.max())
        p /= p.sum()
        top = np.argsort(-p, kind="stable")[:layer.top_k]
        g = p[top] / p[top].sum() if layer.normalize else p[top]
        for e, w in zip(top, g):
            a = wg[e] @ row
            out[t] += w * ((a / (1 + np.exp(-a)) * (wu[e] @ row)) @ wd[e])
    return out


@pytest.mark.parametrize("top_k,everything_to_one", [(4, False), (1, True),
                                                     (4, True)])
def test_dropless_layer_against_a_loop_over_tokens(top_k,
                                                   everything_to_one):
    """No capacity and no dropped token, also when one expert is given
    every token."""
    paddle.seed(1)
    layer = DroplessMoE(32, 16, 8, top_k)
    if everything_to_one:
        # the router's column of expert 5 dominates every score
        w = np.asarray(layer.router._value).copy()
        w[:, 5] = 0
        layer.router._value = jnp.asarray(w)
    x = np.random.default_rng(0).normal(size=(40, 32)).astype(np.float32)
    if everything_to_one:
        x[:, 0] = 30.0
        w = np.asarray(layer.router._value).copy()
        w[0, 5] = 1.0
        layer.router._value = jnp.asarray(w)
    out, chosen, stats = layer.run(jnp.asarray(x))
    if everything_to_one:
        assert (np.asarray(chosen)[:, 0] == 5).all()
        assert int(stats.assignments_max) == 40
    assert int(stats.assignments) == 40 * top_k
    want = _loop_over_tokens(layer, x)
    assert np.abs(np.asarray(out) - want).max() < 1e-4 * np.abs(want).max()


def test_shares_of_eight_holders_add_up_to_the_whole_layer():
    """A layer that is told which experts it holds routes over all of
    them and computes its own experts' part: the parts of 8 holders of 2
    of 16 experts each are the whole layer's output."""
    paddle.seed(2)
    whole = DroplessMoE(32, 16, 16, 4)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(24, 32)),
                    jnp.float32)
    want, chosen, stats = whole.run(x)
    total = np.zeros(want.shape, np.float64)
    read = 0
    for h in range(8):
        held = (2 * h, 2 * h + 1)
        part = DroplessMoE(32, 16, 16, 4, held=held)
        part.router._value = whole.router._value
        for name in ("w_gate", "w_up", "w_down"):
            getattr(part, name)._value = getattr(whole, name)._value[
                jnp.asarray(held)]
        out, chose, st = part.run(x)
        assert (np.asarray(chose) == np.asarray(chosen)).all()
        total += np.asarray(out, np.float64)
        read += int(st.assignments)
    assert read == int(stats.assignments) == 24 * 4
    assert np.abs(total - np.asarray(want)).max() \
        < 1e-5 * np.abs(np.asarray(want)).max()


def test_a_token_left_out_reads_no_expert():
    paddle.seed(3)
    layer = DroplessMoE(32, 16, 8, 2)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(6, 32)),
                    jnp.float32)
    valid = jnp.asarray([True, False, True, False, False, True])
    out, _, stats = layer.run(x, valid)
    full, _, _ = layer.run(x)
    assert int(stats.assignments) == 3 * 2
    assert np.abs(np.asarray(out)[1]).max() == 0
    assert np.allclose(np.asarray(out)[0], np.asarray(full)[0], atol=1e-6)


# ------------------------------------------- kernels, interpret mode
def test_grouped_experts_kernel_in_interpret_mode():
    rng = np.random.default_rng(4)
    T, H, M, E, K = 24, 128, 256, 8, 2
    x = jnp.asarray(rng.normal(size=(T, H)), jnp.float32)
    w = [jnp.asarray(rng.normal(size=(E, M, H)) / 10, jnp.float32)
         for _ in range(3)]
    chosen, gates = moe_experts.route_topk(
        x, jnp.asarray(rng.normal(size=(H, E)), jnp.float32), K)
    valid = jnp.asarray(rng.random(T) < 0.8)
    got, s1 = moe_experts.grouped_experts(x, chosen, gates, *w,
                                          token_valid=valid,
                                          use_pallas=True, interpret=True)
    want, s2 = moe_experts.grouped_experts(x, chosen, gates, *w,
                                           token_valid=valid,
                                           use_pallas=False)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-4
    assert [int(v) for v in s1] == [int(v) for v in s2]


def test_block_causal_mask_in_the_chunk_kernel_interpret_mode():
    rng = np.random.default_rng(5)
    B, T, H, KVH, D, bs, nb = 1, 16, 4, 2, 16, 8, 6
    q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(nb, bs, KVH, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nb, bs, KVH, D)), jnp.float32)
    bt = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    start = jnp.asarray([8], jnp.int32)
    outs = {mb: [np.asarray(fused_chunked_attention(
        q, kp, vp, bt, start, use_pallas=p, interpret=True, mask_block=mb))
        for p in (True, False)] for mb in (1, 4)}
    for pallas, xla in outs.values():
        assert np.abs(pallas - xla).max() < 1e-5
    # a block's first position sees its whole block: not the causal answer
    assert np.abs(outs[4][1][0, 0] - outs[1][1][0, 0]).max() > 1e-3
    assert np.abs(outs[4][1][0, 3] - outs[1][1][0, 3]).max() < 1e-6


def test_context_partials_in_interpret_mode():
    rng = np.random.default_rng(6)
    B, KVH, R, D, bs, nb = 3, 2, 8, 16, 8, 12
    q = jnp.asarray(rng.normal(size=(B, KVH, R, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(nb, bs, KVH, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nb, bs, KVH, D)), jnp.float32)
    bt = jnp.asarray(rng.integers(1, nb, size=(B, 4)), jnp.int32)
    last = jnp.asarray([0, 13, 31], jnp.int32)
    from paddle_tpu.kernels.paged_attention import _combine_splits

    got = _combine_splits(*paged_context_partials(
        q, kp, vp, bt, last, use_pallas=True, interpret=True))
    want = _combine_splits(*paged_context_partials(
        q, kp, vp, bt, last, use_pallas=False))
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5


def test_chunk_and_block_programs_with_the_kernels_in_interpret_mode():
    """The whole served path (q/k norm, block-causal chunk kernel, the
    context walk, the grouped experts) with every Pallas kernel in
    interpret mode against the XLA path."""
    L = 4
    results = []
    for interpret in (False, True):
        model = _model(block_length=L, denoising_steps=2,
                       remasking="low_confidence_static")
        eng = _engine(model, fused_kernels=True)
        with force_pallas_interpret(interpret):
            chunk = make_chunked_prefill_step(model, fused=True)
            block = make_paged_block_step(model, fused=True)
            S, C = eng.config.max_batch_size, eng.chunk_tokens
            table = np.zeros((S, eng.max_blocks_per_seq), np.int32)
            table[0, :4] = np.arange(1, 5)
            ids = np.zeros((1, C), np.int32)
            ids[0, :12] = np.arange(1, 13)
            _, pools = chunk(ids, eng.pool.layers, table[:1],
                             np.asarray([0], np.int32), np.int32(11))
            bids = np.full((S, L), 7, np.int32)
            masked = np.zeros((S, L), bool)
            masked[0, 2:] = True
            zeros = np.zeros((S,), np.int32)
            start, mode, n = zeros.copy(), zeros.copy(), zeros.copy()
            start[0], mode[0], n[0] = 12, 1, 1
            small, probe, _ = block(bids, masked, start, mode, n,
                                    np.full((S,), 2.0, np.float32), pools,
                                    table)
            results.append((np.asarray(small), np.asarray(probe["logits"])))
    (s0, l0), (s1, l1) = results
    assert np.abs(l0 - l1).max() < 1e-4 * np.abs(l0).max()
    assert (s0 == s1).all()


def test_leaving_the_q_k_norm_out_changes_the_logits(monkeypatch):
    """The norm on q and k is in the served path: without it the block
    program no longer agrees with the reference."""
    model = _model()
    ids = np.arange(1, 17, dtype=np.int32)[None]
    want = np.asarray(reference.logits(reference.weights_of(model),
                                       _cfg(model), ids[0], 16))
    got = np.asarray(model(ids)._value)[0]
    assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()
    monkeypatch.setattr(SDARMoEForCausalLM, "_qk_norm",
                        lambda self, attn, q, k: (q, k))
    off = np.asarray(model(ids)._value)[0]
    assert np.abs(off - want).max() > 1e-2 * np.abs(want).max()
