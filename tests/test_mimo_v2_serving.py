"""A model with WINDOW and FULL attention layers of different head counts,
keys wider than their values, a partial rotation, a SINK logit in the
window layers' softmax and a sigmoid-routed mixture of experts of which
the replica holds a share, served by the normal engine from a pool of
K/V records of two geometries (models/mimo_v2.py, serving/cache.py, the
``sink=``/``rotary=`` and value widths of the two serving kernels), at
tiny widths on the CPU with seeded random weights, against the plain
reference (``benchmarks/reference/mimo_v2.py``, the one the benchmark's
``correct`` uses).  Logits are compared, never greedy tokens
(``tests/logit_check.py``)."""
import hashlib
import math

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.kernels.chunked_prefill import fused_chunked_attention
from paddle_tpu.kernels.paged_attention import fused_paged_decode
from paddle_tpu.models import (DroplessMoE, Glm4MoeLiteConfig,
                               Glm4MoeLiteForCausalLM, MimoV2Config,
                               MimoV2ForCausalLM)
from paddle_tpu.models import mimo_v2
from paddle_tpu.models.generation import (make_chunked_prefill_step,
                                          make_paged_decode_step)
from paddle_tpu.models.llama import apply_rope, paged_scatter, precompute_rope
from paddle_tpu.serving import Engine, ServingConfig
from paddle_tpu.serving.cache import BlockKVPool, LayerCache, describe_cache
from benchmarks.reference import mimo_v2 as reference
from logit_check import assert_logits_within
from test_afmoe_serving import (STEP_PROGRAM_SHA256, _ByHand,
                                _step_program_texts)
from test_glm4_moe_lite_serving import (AFMOE_PROGRAM_SHA256,
                                        _afmoe_program_texts)

WINDOW = 32
_KEYS = (
    "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
    "v_head_dim", "swa_num_attention_heads", "swa_num_key_value_heads",
    "swa_head_dim", "swa_v_head_dim", "sliding_window", "rope_theta",
    "swa_rope_theta", "partial_rotary_factor", "layernorm_epsilon",
    "attention_value_scale", "num_experts_per_tok", "norm_topk_prob",
    "routed_scaling_factor", "add_swa_attention_sink_bias",
    "add_full_attention_sink_bias")


def _model(seed=0, **overrides):
    paddle.seed(seed)
    model = MimoV2ForCausalLM(MimoV2Config.tiny(**overrides))
    model.eval()
    return model


def _cfg(model):
    """The model's settings as a configuration file gives them."""
    return {k: getattr(model.config, k) for k in _KEYS}


def _engine(model, **kw):
    kw = dict(dict(max_batch_size=3, block_size=8, num_blocks=64,
                   chunk_tokens=16, max_model_len=200), **kw)
    return Engine(model, ServingConfig(**kw))


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(1, 250, size=n,
                                                dtype=np.int32)


def _served_against_the_reference(model, prompt, feed, **engine_kw):
    """``(got, want, report)``: a row through the engine's own chunk
    and decode programs under the manager's tables, and the reference's
    full forward of it under the witness of what those programs chose."""
    eng = _engine(model, **engine_kw)
    hand = _ByHand(eng)
    got = hand.row(0, prompt, feed)
    row = np.concatenate([prompt, feed])
    chose = mimo_v2.routing_witness(model, eng, row, hand.full[0])
    want, report = reference.logits(
        reference.weights_of(model), _cfg(model), row, last=1 + len(feed),
        witness=chose)
    return got, np.asarray(want), report, hand


# ------------------------------------------------- against the reference
@pytest.mark.parametrize("seed", [0, 1])
def test_full_forward_against_the_reference(seed):
    """Every layer under its own mask, heads, rotation and sink: three
    windows of context."""
    model = _model(seed)
    tokens = _tokens(3 * WINDOW + 5, seed)
    got = np.asarray(model(paddle.to_tensor(tokens[None]))._value[0])
    want = np.asarray(reference.logits(
        reference.weights_of(model), _cfg(model), tokens, last=len(tokens)))
    assert_logits_within(got, want, 2e-5, "full forward")


@pytest.mark.parametrize("block,chunk,held", [
    (8, 16, None), (4, 24, None), (16, 16, tuple(range(0, 16, 2)))])
def test_chunked_prefill_then_decode_against_the_reference(block, chunk,
                                                           held):
    """A sequence several windows long through the engine's own chunk
    and decode programs, more decode steps than the window (by the last
    one every key a window layer reads was written by the decode
    program), pages released behind the window as the manager releases
    them, against one full forward of the row with the window as a mask;
    the reference replays what the programs chose.  The last case holds
    half the experts: both sides compute that share alone."""
    model = _model(2, held_experts=held)
    prompt, feed = _tokens(70, 3), _tokens(WINDOW + 8, 4)
    got, want, report, hand = _served_against_the_reference(
        model, prompt, feed, block_size=block, chunk_tokens=chunk)
    assert_logits_within(got, want, 2e-5, "chunks then decode")
    routed = sum(1 for layer in model.model.layers if layer.routed)
    assert report["ok"] and report["not_first_choice"] == 0
    assert report["decisions"] == routed * (len(prompt) + len(feed)) * 4
    assert hand.released, "the window moved past pages"
    assert hand.most_pages <= hand.pool.window_pages_per_seq \
        == -(-(WINDOW + chunk) // block) + 1


def test_the_engine_s_tokens_are_the_reference_s_and_its_counters_add_up():
    """What ``Engine.step()`` emits is the argmax of logits the
    reference agrees with; the routing and chunk counters hold what the
    programs and the schedule did."""
    from logit_check import decided

    held = tuple(range(4))
    model = _model(3, held_experts=held)
    eng = _engine(model)
    prompt = _tokens(80, 30)
    req = eng.submit(prompt, max_new_tokens=12)
    eng.run_until_complete()
    row = np.concatenate([prompt, req.generated])
    want = np.asarray(reference.logits(
        reference.weights_of(model), _cfg(model), row[:-1], last=12))
    sure = decided(want, 1e-4)
    assert sure.any()
    assert (want.argmax(-1) == np.asarray(req.generated))[sure].all()
    c = eng.stats()["counters"]
    routed, k = 2, model.config.num_experts_per_tok
    # every real token of every run, routed layer and choice; 4 of 16
    # experts held: far fewer land here
    assert c["route_picks"] == routed * k * (80 + 11)
    assert c["route_picks_decode"] == routed * k * 11
    assert 0 < c["held_picks"] < c["route_picks"]
    assert 0 <= c["held_picks_decode"] <= c["route_picks_decode"]
    assert c["held_picks"] == c["expert_assignments"]
    # a query at p sees min(p + 1, window) keys of a window layer
    assert c["prefill_window_pairs"] == sum(min(p + 1, WINDOW)
                                            for p in range(80))
    assert c["prefill_attended_pairs"] == 80 * 81 // 2
    eng.pool.check_leaks()
    assert eng.decode_cache_size() == 1 and eng.prefill_cache_size() == 1


# ------------------------------------------------ faults that must fail
def _window_as_full(m):
    from paddle_tpu.kernels import chunked_prefill, paged_attention

    chunked = chunked_prefill.fused_chunked_attention
    decode = paged_attention.fused_paged_decode
    m.setattr(chunked_prefill, "fused_chunked_attention",
              lambda *a, window=None, **kw: chunked(*a, **kw))
    m.setattr(paged_attention, "fused_paged_decode",
              lambda *a, window=None, **kw: decode(*a, **kw))
    # (every page kept: a full layer's walk would read released ones)
    m.setattr(BlockKVPool, "window_first_page", lambda self, pos: 0)


def _every_lane_rotated(m, model):
    for layer in model.model.layers:
        m.setattr(layer, "rotary", layer.self_attn.head_dim)
    tables = {}
    for layer in model.model.layers:
        shape = model.config.attention_shape(layer.kind)
        tables[layer.kind] = precompute_rope(
            shape["head_dim"], model.config.max_position_embeddings,
            shape["theta"])
    m.setattr(model.model, "rope", lambda layer: tables[layer.kind])


def _the_other_kind_s_theta(m, model):
    rope = type(model.model).rope
    other = {layer.kind: layer for layer in model.model.layers}
    m.setattr(model.model, "rope", lambda layer: rope(
        model.model, other[1 - layer.kind]))


FAULTS = {
    "sink left out": lambda m, model: m.setattr(
        mimo_v2, "_sink", lambda layer: None),
    "window layers run as full": lambda m, model: _window_as_full(m),
    "every lane rotated": _every_lane_rotated,
    "the other kind's theta": _the_other_kind_s_theta,
    "value scale left out": lambda m, model: m.setattr(
        model.config, "attention_value_scale", 1.0),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_moves_the_logits_past_the_limit(fault, monkeypatch):
    """The comparison above is tight enough to see each mechanism: with
    one of them planted wrong in the served path the same check fails
    (the reference is left as it is)."""
    model = _model(2)
    prompt, feed = _tokens(70, 3), _tokens(4, 4)
    want = np.asarray(reference.logits(
        reference.weights_of(model), _cfg(model),
        np.concatenate([prompt, feed]), last=1 + len(feed)))
    FAULTS[fault](monkeypatch, model)
    got = _ByHand(_engine(model, chunk_tokens=200 if "full" in fault
                          else 16)).row(0, prompt, feed)
    with pytest.raises(AssertionError, match="of the largest logit"):
        assert_logits_within(got, want, 1e-3, fault)


# ------------------------------------------------- the experts' shares
EXPERTS, HOLDERS = 64, 16


def _routed_layer(seed, held=None):
    paddle.seed(seed)
    return DroplessMoE(32, 16, EXPERTS, 8, held=held, scores="sigmoid",
                       selection_bias=True, route_scale=1.0, norm_eps=1e-20)


def test_sixteen_held_shares_add_up_to_the_uncut_layer():
    """16 holders of 4 of 64 experts each, every one routing over all
    64: their parts add up to the uncut layer's output, and their held
    choices to all the router's."""
    whole = _routed_layer(2)
    bias = jnp.asarray(np.random.default_rng(0).normal(size=EXPERTS) * 0.02,
                       jnp.float32)
    whole.expert_bias._value = bias
    x = jnp.asarray(np.random.default_rng(1).normal(size=(24, 32)),
                    jnp.float32)
    want, chosen, stats = whole.run(x)
    want = np.asarray(want, np.float64)
    total, held_picks = np.zeros_like(want), 0
    each = EXPERTS // HOLDERS
    for h in range(HOLDERS):
        held = tuple(range(each * h, each * (h + 1)))
        part = _routed_layer(2, held=held)
        part.router._value = whole.router._value
        part.expert_bias._value = bias
        for name in ("w_gate", "w_up", "w_down"):
            getattr(part, name)._value = getattr(whole, name)._value[
                jnp.asarray(held)]
        out, chose, st = part.run(x)
        assert (np.asarray(chose) == np.asarray(chosen)).all()
        total += np.asarray(out, np.float64)
        held_picks += int(st.assignments)
    assert held_picks == int(stats.assignments) == 24 * 8
    assert np.abs(total - want).max() < 1e-5 * np.abs(want).max()


# ---------------------------------------------------------- the kernels
def _dense_attention(q, keys, values, q_pos, window, sink):
    """``q [H, D]`` at ``q_pos`` over ``keys [L, KVH, D]``, ``values [L,
    KVH, Dv]``; ``sink [H]`` or None."""
    lo = 0 if window is None else max(0, q_pos - window + 1)
    rep = q.shape[0] // keys.shape[1]
    out = np.zeros((q.shape[0], values.shape[-1]))
    for h in range(q.shape[0]):
        s = keys[lo:q_pos + 1, h // rep] @ q[h] / math.sqrt(q.shape[1])
        m = s.max() if sink is None else max(s.max(), sink[h])
        w = np.exp(s - m)
        total = w.sum() + (0.0 if sink is None else np.exp(sink[h] - m))
        out[h] = (w / total) @ values[lo:q_pos + 1, h // rep]
    return out


def _rotated(x, cos, sin, at, rotary):
    return np.concatenate([np.asarray(apply_rope(
        x[..., :rotary], cos, sin, at)), np.asarray(x[..., rotary:])], -1)


@pytest.fixture(scope="module")
def paged():
    rng = np.random.default_rng(0)
    B, H, D, DV, bs, nbs, nb = 3, 8, 24, 16, 8, 16, 64
    table = jnp.asarray(rng.permutation(np.arange(1, nb))[:B * nbs]
                        .reshape(B, nbs), jnp.int32)
    return rng, (B, H, D, DV, bs, nb), table


def _pools(rng, nb, bs, kvh, lanes, d, dv):
    k = rng.normal(size=(nb, bs, kvh, lanes))
    k[..., d:] = 0.0                # zeros past the key, as the pool keeps
    return (jnp.asarray(k, jnp.float32),
            jnp.asarray(rng.normal(size=(nb, bs, kvh, dv)), jnp.float32))


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
@pytest.mark.parametrize("kvh,window,sink,lanes", [
    (1, 20, True, 24), (2, 20, True, 32), (2, None, False, 24),
    (1, None, True, 32), (2, 5, False, 24)])
def test_decode_kernel_with_a_sink_narrow_values_and_a_partial_rotation(
        paged, kvh, window, sink, lanes, use_pallas):
    """24-wide keys (in 24 or 32 lanes), 16-wide values, 8 of 24 lanes
    turned, 1 or 2 KV heads, a window or none, a sink or none: against
    the dense softmax with the sink in its denominator."""
    rng, (B, H, D, DV, bs, nb), table = paged
    k_pool, v_pool = _pools(rng, nb, bs, kvh, lanes, D, DV)
    rotary = 8
    cos, sin = precompute_rope(rotary, 256, 10000.0)
    sinks = jnp.asarray(rng.normal(size=H) + 2.0, jnp.float32) \
        if sink else None
    pos = jnp.asarray([5, 37, 100], jnp.int32)
    q, k_new = (jnp.asarray(rng.normal(size=(B, 1, n, D)), jnp.float32)
                for n in (H, kvh))
    v_new = jnp.asarray(rng.normal(size=(B, 1, kvh, DV)), jnp.float32)
    out, new_k, new_v = fused_paged_decode(
        q, k_new, v_new, k_pool, v_pool, table, pos, cos, sin,
        window=window, sink=sinks, rotary=rotary, use_pallas=use_pallas,
        interpret=True)
    assert out.shape == (B, 1, H, DV) and new_k.shape[-1] == lanes
    for b in range(B):
        p = int(pos[b])
        at = jnp.asarray([p])
        qr, kr = (_rotated(t[b:b + 1], cos, sin, at, rotary)[0, 0]
                  for t in (q, k_new))
        keys, values = (np.concatenate([np.asarray(pool)[int(i)]
                                        for i in table[b]])
                        for pool in (new_k, new_v))
        assert np.abs(keys[p, :, :D] - kr).max() < 1e-6
        assert not keys[:, :, D:].any()
        want = _dense_attention(qr, keys[..., :D], values, p, window,
                                None if sinks is None else np.asarray(sinks))
        assert np.abs(np.asarray(out)[b, 0] - want).max() < 2e-6


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
@pytest.mark.parametrize("kvh,window,sink,lanes", [
    (1, 20, True, 32), (2, 5, True, 24), (2, None, False, 32),
    (1, 130, True, 24)])
def test_chunk_kernel_with_a_sink_and_narrow_values(paged, kvh, window,
                                                    sink, lanes,
                                                    use_pallas):
    rng, (B, H, D, DV, bs, nb), table = paged
    k_pool, v_pool = _pools(rng, nb, bs, kvh, lanes, D, DV)
    T = 16
    sinks = jnp.asarray(rng.normal(size=H) + 2.0, jnp.float32) \
        if sink else None
    starts = jnp.asarray([0, 24, 96], jnp.int32)
    q, k = (jnp.asarray(rng.normal(size=(B, T, n, D)), jnp.float32)
            for n in (H, kvh))
    v = jnp.asarray(rng.normal(size=(B, T, kvh, DV)), jnp.float32)
    at = starts[:, None] + jnp.arange(T)
    k_pool = paged_scatter(k_pool, jnp.pad(
        k, ((0, 0), (0, 0), (0, 0), (0, lanes - D))), table, at)
    v_pool = paged_scatter(v_pool, v, table, at)
    out = np.asarray(fused_chunked_attention(
        q, k_pool, v_pool, table, starts, window=window, sink=sinks,
        use_pallas=use_pallas, interpret=True))
    assert out.shape == (B, T, H, DV)
    for b in range(B):
        keys, values = (np.concatenate([np.asarray(pool)[int(i)]
                                        for i in table[b]])
                        for pool in (k_pool, v_pool))
        for t in range(T):
            want = _dense_attention(
                np.asarray(q[b, t]), keys[..., :D], values,
                int(starts[b]) + t, window,
                None if sinks is None else np.asarray(sinks))
            assert np.abs(out[b, t] - want).max() < 2e-6


# ------------------------------------------------------------ the cache
def test_the_model_describes_its_cache_in_two_geometries():
    """Full layers of 1 KV head and window layers of 2, keys of 24 and
    values of 16; a key of 192 is stored in 256 lanes; every byte count
    reads the layer's own record."""
    model = _model(0)
    records = describe_cache(model)
    assert [r.kind for r in records] == ["full", "window", "full"]
    assert [(r.kv_heads, r.head_dim, r.value_width, r.key_lanes)
            for r in records] == [(1, 24, 16, 24), (2, 24, 16, 24),
                                  (1, 24, 16, 24)]
    assert [len(r.sidecars) for r in records] == [0, 1, 1]
    eng = _engine(model)
    pool = eng.pool
    shapes = [tuple(a.shape) for entry in pool.layers for a in entry[:2]]
    w = pool.window.num_blocks
    assert shapes == [(64, 8, 1, 24), (64, 8, 1, 16), (w, 8, 2, 24),
                      (w, 8, 2, 16), (64, 8, 1, 24), (64, 8, 1, 16)]
    # a block of the full group: two full layers' K/V (f32) and two
    # routed layers' witness of 4 ids a position
    assert pool.block_bytes() == 2 * 8 * 1 * (24 + 16) * 4 + 2 * 8 * 4 * 4
    assert pool.window_bytes() == w * 8 * 2 * (24 + 16) * 4
    assert eng.stats()["pool"]["window_bytes"] == pool.window_bytes()
    wide = LayerCache(4, 192, jnp.bfloat16, v_head_dim=128)
    assert (wide.key_lanes, wide.value_width) == (256, 128)
    assert wide.page_bytes(16) == 16 * 4 * (256 + 128) * 2
    assert LayerCache(8, 128, jnp.bfloat16).key_lanes == 128
    with pytest.raises(ValueError, match="latent record"):
        LayerCache(1, 576, jnp.bfloat16, value_dim=512, v_head_dim=256)


def test_the_engine_budget_reads_each_layer_s_record():
    model = _model(0)
    per_block = sum(c.block_bytes(8) for c in describe_cache(model))
    eng = _engine(model, num_blocks=None, kv_pool_bytes=40 * per_block)
    assert eng.num_blocks == 40 and eng.pool.block_bytes() == per_block


def test_what_this_model_is_not_served_with_is_refused():
    model = _model(0)
    for kw, what in ((dict(speculative=_model(1)), "speculative decoding"),
                     (dict(kv_cache_dtype="int8"), "a quantized KV cache"),
                     (dict(mesh={"tp": 1}), "a runtime mesh")):
        with pytest.raises(ValueError, match=f"{what} is not supported"):
            _engine(model, **kw)
    with pytest.raises(ValueError, match="sampling is not supported yet"):
        _engine(model).submit(_tokens(5, 0), temperature=0.8, seed=1)
    with pytest.raises(ValueError, match="shared expert"):
        MimoV2Config.tiny(n_shared_experts=1)
    with pytest.raises(ValueError, match="hybrid_layer_pattern"):
        MimoV2Config.tiny(hybrid_layer_pattern=(0, 1))


def test_the_rotary_width_is_even():
    assert mimo_v2.rotary_dim(192, 0.334) == 64
    assert mimo_v2.rotary_dim(16, 0.334) == 4       # not int(5.3)
    assert reference.rotary_lanes(16, 0.334) == 4
    # a published list longer than the model is a cut model's: its
    # first entries are kept
    cfg = MimoV2Config.tiny(num_hidden_layers=2,
                            hybrid_layer_pattern=(0, 1, 1, 1, 1, 1, 0),
                            moe_layer_freq=(0, 1, 1, 1, 1, 1, 1))
    assert cfg.hybrid_layer_pattern == (0, 1)
    assert cfg.moe_layer_freq == (0, 1)


# ------------------- the models that were served before: as they were
# sha256 of the lowered text of the tiny latent model's two step programs,
# taken on the commit before the K/V kernels gained ``sink=``, ``rotary=``
# and a value width of their own (its kernels import the decode kernel's
# split-K combine); the Mistral-shaped, block and window models' are the
# tables imported above.  A change that is MEANT to alter those programs
# renews the hashes (the assertion prints them).
GLM_PROGRAM_SHA256 = {
    "glm.chunk":
        "906d473eb667056a3cd57b17b603c30ec8b191b684a079f49b6e92aa53e44894",
    "glm.decode":
        "16439bba2562be4c72273f2640d907020909ca43e1d10e74fd39c723cbd4e558",
}


def _glm_program_texts():
    paddle.seed(0)
    model = Glm4MoeLiteForCausalLM(Glm4MoeLiteConfig.tiny())
    model.eval()
    S, nb, C = 2, 8, 16
    eng = Engine(model, ServingConfig(
        max_batch_size=S, block_size=8, num_blocks=16, chunk_tokens=C,
        max_model_len=64))
    table = np.zeros((S, nb), np.int32)
    zeros = np.zeros((S,), np.int32)
    return {
        "glm.chunk": make_chunked_prefill_step(model).lower(
            np.zeros((1, C), np.int32), eng.pool.layers, table[:1],
            zeros[:1], np.int32(0)).as_text(),
        "glm.decode": make_paged_decode_step(model).lower(
            np.zeros((S, 1), np.int32), eng.pool.layers, table,
            zeros).as_text()}


@pytest.mark.parametrize("family", ["llama-sdar", "afmoe", "glm"])
def test_default_arguments_lower_to_the_text_of_before(family):
    texts, want = {
        "llama-sdar": (_step_program_texts, STEP_PROGRAM_SHA256),
        "afmoe": (_afmoe_program_texts, AFMOE_PROGRAM_SHA256),
        "glm": (_glm_program_texts, GLM_PROGRAM_SHA256)}[family]
    got = {name: hashlib.sha256(text.encode()).hexdigest()
           for name, text in texts().items()}
    assert got == want, got
