"""A model with multi-head LATENT attention (one compressed key/value
and one shared rotary key a position) and a sigmoid-routed mixture of
experts with a shared expert, served by the normal engine from a pool of
latent records with its prefix cache on (models/glm4_moe_lite.py,
serving/cache.py, kernels/latent_attention.py), at tiny widths on the
CPU with seeded random weights, against the plain reference
(``benchmarks/reference/glm4_moe_lite.py``, the one the benchmark's
``correct`` uses, which computes the EXPANDED form).  Logits are
compared, never greedy tokens (``tests/logit_check.py``)."""
import hashlib
import math

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.kernels.latent_attention import (fused_latent_chunk,
                                                 fused_latent_decode,
                                                 latent_pool_lanes)
from paddle_tpu.kernels.moe_experts import route_topk
from paddle_tpu.models import (AfmoeConfig, AfmoeForCausalLM, DroplessMoE,
                               Glm4MoeLiteConfig, Glm4MoeLiteForCausalLM)
from paddle_tpu.models.afmoe import AfmoeMLP
from paddle_tpu.models.generation import (make_chunked_prefill_step,
                                          make_paged_decode_step)
from paddle_tpu.models.glm4_moe_lite import routing_witness
from paddle_tpu.serving import Engine, ServingConfig
from paddle_tpu.serving.cache import BlockKVPool, LayerCache, describe_cache
from benchmarks.reference import glm4_moe_lite as reference
from logit_check import assert_logits_within
from test_afmoe_serving import (STEP_PROGRAM_SHA256, _step_program_texts)

BLOCK, CHUNK = 8, 16


def _model(seed=0, **overrides):
    paddle.seed(seed)
    model = Glm4MoeLiteForCausalLM(Glm4MoeLiteConfig.tiny(**overrides))
    model.eval()
    return model


def _cfg(model):
    c = model.config
    return {k: getattr(c, k) for k in (
        "rms_norm_eps", "num_attention_heads", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_theta",
        "num_experts_per_tok", "n_routed_experts", "norm_topk_prob",
        "routed_scaling_factor", "first_k_dense_replace",
        "num_hidden_layers")}


def _tokens(n, seed=0, vocab=256):
    return np.random.default_rng(seed).integers(1, vocab, size=n,
                                                dtype=np.int32)


def _engine(model, **kw):
    cfg = dict(max_batch_size=2, block_size=BLOCK, num_blocks=48,
               chunk_tokens=CHUNK, max_model_len=128)
    cfg.update(kw)
    return Engine(model, ServingConfig(**cfg))


def _reference_logits(model, tokens, last, witness=None):
    return reference.logits(reference.weights_of(model), _cfg(model),
                            tokens, last=last, witness=witness)


class _ByHand:
    """One sequence through the engine's own step programs on blocks the
    pool's allocator hands out; ``taken`` blocks hold positions that are
    not run again."""

    def __init__(self, eng, rid="by-hand"):
        self.eng, self.rid = eng, rid
        self.chunk = make_chunked_prefill_step(eng.model)
        self.decode = make_paged_decode_step(eng.model)
        S, nb = eng.config.max_batch_size, eng.max_blocks_per_seq
        self.table = np.zeros((S, nb), np.int32)
        self.blocks = []

    def _bind(self, pools):
        self.eng.pool.layers = [tuple(e) for e in pools]

    def run(self, prompt, feed, taken=()):
        eng, pool = self.eng, self.eng.pool
        self.blocks = list(taken)
        self.blocks += pool.allocate(
            self.rid, pool.blocks_for(len(prompt) + len(feed) + 1)
            - len(self.blocks))
        self.table[0, :len(self.blocks)] = self.blocks
        C = eng.chunk_tokens
        for start in range(len(taken) * BLOCK, len(prompt), C):
            n = min(C, len(prompt) - start)
            ids = np.zeros((1, C), np.int32)
            ids[0, :n] = prompt[start:start + n]
            (last, stats), pools = self.chunk(
                ids, pool.layers, self.table[:1].copy(),
                np.asarray([start], np.int32), np.int32(n - 1))
            self._bind(pools)
        out = [np.asarray(last)[0]]
        lengths = np.zeros((eng.config.max_batch_size,), np.int32)
        lengths[0] = len(prompt)
        tok = np.zeros((eng.config.max_batch_size, 1), np.int32)
        for t in feed:
            tok[0, 0] = t
            (logits, stats), pools = self.decode(
                tok.copy(), pool.layers, self.table.copy(), lengths.copy())
            self._bind(pools)
            out.append(np.asarray(logits)[0])
            lengths[0] += 1
        return np.stack(out)


# ------------------------------------------------------------ the model
def test_full_forward_matches_the_reference():
    model = _model(1)
    tokens = _tokens(70, 1)
    got = np.asarray(model(paddle.to_tensor(tokens[None]))._value[0])
    want = np.asarray(_reference_logits(model, tokens, len(tokens)))
    assert_logits_within(got, want, 2e-5, "forward against the reference")


def test_the_absorbed_form_is_the_expanded_form_in_float32():
    """Chunked prefill and decode (absorbed, over latent pages) against
    the model's own full forward (expanded, per-head keys and values)."""
    model = _model(2)
    prompt, feed = _tokens(45, 2), _tokens(4, 3)
    row = np.concatenate([prompt, feed])
    want = np.asarray(model(paddle.to_tensor(row[None]))._value[0])[
        len(prompt) - 1:]
    eng = _engine(model)
    got = _ByHand(eng).run(prompt, feed)
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
    eng.pool.free_request("by-hand")
    eng.pool.check_leaks()


def test_chunked_prefill_then_decode_against_the_reference():
    model = _model(3)
    prompt, feed = _tokens(53, 4), _tokens(3, 5)
    eng = _engine(model)
    by_hand = _ByHand(eng)
    got = by_hand.run(prompt, feed)
    row = np.concatenate([prompt, feed])
    chose = routing_witness(model, eng, row, by_hand.table[0])
    assert chose.shape == (2, len(row), 4)
    want, report = _reference_logits(model, row, 1 + len(feed), chose)
    assert report["ok"] and report["decisions"] == 2 * len(row) * 4
    assert_logits_within(got, np.asarray(want), 2e-5, "served path")


def test_a_mechanism_left_out_changes_the_logits(monkeypatch):
    """Each of the model's own mechanisms is seen by the comparison with
    the reference: the latent norm, the key's rotation, which lanes are
    the value, the gates' scale, the shared expert."""
    M = Glm4MoeLiteForCausalLM
    model = _model(4)
    prompt, feed = _tokens(40, 6), _tokens(2, 7)
    row = np.concatenate([prompt, feed])
    want = np.asarray(_reference_logits(model, row, 1 + len(feed)))

    def served():
        vars(model).pop("_compiled_steps", None)
        eng = _engine(model)
        got = _ByHand(eng).run(prompt, feed)
        eng.pool.free_request("by-hand")
        return np.abs(got - want).max() / np.abs(want).max()

    assert served() < 2e-5
    entry = M._entry
    shared = AfmoeMLP.run
    from paddle_tpu.models import glm4_moe_lite as module

    rms = module._rms
    faults = {
        "the key left unrotated": lambda m: m.setattr(
            M, "_rotate_key", staticmethod(lambda k, c, s, start: k)),
        "the value read from the wrong lanes": lambda m: m.setattr(
            M, "_entry", lambda self, c_kv, k_rope, lanes: entry(
                self, jnp.roll(c_kv, 1, axis=-1), k_rope, lanes)),
        "the latent norm left out": lambda m: m.setattr(
            module, "_rms", lambda x, w, eps: x * w
            if x.shape[-1] == model.config.kv_lora_rank
            else rms(x, w, eps)),
        "the shared expert dropped": lambda m: m.setattr(
            AfmoeMLP, "run", lambda self, x, nw, eps: jnp.zeros_like(x)
            if self.gate_proj.weight.shape[1] == 32
            else shared(self, x, nw, eps)),
    }
    for what, plant in faults.items():
        with monkeypatch.context() as m:
            plant(m)
            assert served() > 1e-3, what
    assert served() < 2e-5
    # routed_scaling_factor dropped: a model that scales by 1
    other = _model(4, routed_scaling_factor=1.0)
    eng = _engine(other)
    got = _ByHand(eng).run(prompt, feed)
    assert np.abs(got - want).max() / np.abs(want).max() > 1e-3


# ------------------------------------------------------------- the pool
def test_the_pool_is_built_from_latent_records():
    model = _model()
    records = describe_cache(model)
    assert [r.kind for r in records] == ["latent"] * 3
    assert [len(r.sidecars) for r in records] == [0, 1, 1]
    eng = _engine(model)
    lanes = latent_pool_lanes(model.config.latent_dim)
    assert lanes == 128 and latent_pool_lanes(576) == 640
    assert [tuple(a.shape for a in e) for e in eng.pool.layers] == [
        ((48, BLOCK, lanes),), ((48, BLOCK, lanes), (48, BLOCK * 4)),
        ((48, BLOCK, lanes), (48, BLOCK * 4))]
    # what the leaves take, sidecars included
    per_block = sum(int(a.nbytes) for e in eng.pool.layers for a in e) // 48
    assert eng.pool.block_bytes() == per_block == BLOCK * (
        3 * lanes * 4 + 2 * 4 * 4)
    assert eng.pool.stats()["block_bytes"] == per_block
    assert eng.pool.enable_prefix_cache and eng.pool.window is None
    # a budget in bytes sizes the pool by the same count
    sized = _engine(model, num_blocks=None, kv_pool_bytes=20 * per_block)
    assert sized.num_blocks == 20
    # at the published widths: 6 layers of 640 lanes and 5 witnesses
    published = [LayerCache(1, 576, jnp.bfloat16, value_dim=512,
                            sidecars=(((4,), jnp.int32),) if i else ())
                 for i in range(6)]
    assert sum(r.block_bytes(16) for r in published) // 16 == 7760
    assert 6 * 576 * 2 + 5 * 16 == 6992        # were nothing padded
    with pytest.raises(ValueError, match="latent record"):
        LayerCache(2, 64, jnp.float32, value_dim=48)
    with pytest.raises(ValueError, match="latent"):
        BlockKVPool(2, 8, 8, 1, 64, layer_caches=[
            LayerCache(1, 64, jnp.float32, value_dim=48),
            LayerCache(2, 16, jnp.float32)])


def test_a_shared_prefix_is_served_from_cached_latent_pages():
    model = _model(5)
    document, q1, q2 = _tokens(43, 8), _tokens(6, 9), _tokens(9, 10)
    first, second = (np.concatenate([document, q]) for q in (q1, q2))
    eng = _engine(model)

    def ask(engine, prompt):
        handle = engine.submit(prompt, max_new_tokens=3)
        while engine.has_work():
            engine.step()
        assert handle.finish_reason == "length"
        return handle

    ask(eng, first)
    before = eng.metrics.as_dict()["counters"]
    reused = ask(eng, second)
    after = eng.metrics.as_dict()["counters"]
    shared = (len(document) // BLOCK) * BLOCK
    assert after["cached_prompt_tokens"] - before["cached_prompt_tokens"] \
        == shared
    assert after["prompt_tokens"] - before["prompt_tokens"] == len(second)
    # only what was not cached was prefilled: one chunk
    assert after["prefill_chunks_run"] - before["prefill_chunks_run"] == 1
    assert after["prefill_attended_pairs"] \
        - before["prefill_attended_pairs"] == sum(
            range(shared + 1, len(second) + 1))
    eng.pool.check_leaks()
    # the logits of the reused request are a fresh engine's
    fresh = _engine(model)
    want = ask(fresh, second)
    assert reused.generated == want.generated
    # ... by logits, through the programs, on the matched blocks
    pool = eng.pool
    # (the second request registered its own full blocks in its turn)
    matched = pool.match_prefix(second)
    assert len(matched) == len(second) // BLOCK
    pool.acquire("by-hand", matched)
    feed = _tokens(2, 11)
    got = _ByHand(eng).run(second, feed, taken=matched)
    row = np.concatenate([second, feed])
    ref = np.asarray(_reference_logits(model, row, 1 + len(feed)))
    assert_logits_within(got, ref, 2e-5, "over cached latent pages")
    pool.free_request("by-hand")
    pool.check_leaks()


def test_copy_on_write_moves_a_latent_block_and_its_sidecar_rows():
    model = _model(6)
    eng = _engine(model)
    prompt = _tokens(2 * BLOCK, 12)          # every block full: all shared
    by_hand = _ByHand(eng, "a")
    by_hand.run(prompt, ())
    pool = eng.pool
    pool.register_prefix("a", prompt, by_hand.blocks)
    src = by_hand.blocks[1]
    pool.acquire("b", [src])
    before = [[np.array(a[src]) for a in e] for e in pool.layers]
    assert any(np.abs(rows[0]).max() > 0 for rows in before)
    assert any(rows[1].max() > 0 for rows in before if len(rows) > 1)
    new = pool.ensure_writable("b", src)
    assert new != src and pool.cow_copies == 1
    for rows, entry in zip(before, pool.layers):
        for old, leaf in zip(rows, entry):
            assert np.array_equal(np.asarray(leaf[new]), old)
            assert np.array_equal(np.asarray(leaf[src]), old)
    pool.free_request("a")
    pool.free_request("b")
    pool.check_leaks()


def test_check_leaks_finds_a_leaked_latent_block():
    eng = _engine(_model())
    eng.pool.allocate("lost", 2)
    with pytest.raises(AssertionError, match="leaked"):
        eng.pool.check_leaks()
    eng.pool.free_request("lost")
    eng.pool.check_leaks()


def test_preemption_recomputes_and_revive_rebuilds_the_pool():
    """A pool too small for both requests' whole lives: the younger is
    preempted and recomputed, and both end with the tokens they have
    alone; a revived engine serves again."""
    model = _model(7)
    prompts = [_tokens(30, 13), _tokens(28, 14)]

    def alone(prompt):
        eng = _engine(model)
        h = eng.submit(prompt, max_new_tokens=20)
        while eng.has_work():
            eng.step()
        return h.generated

    want = [alone(p) for p in prompts]
    eng = _engine(model, num_blocks=12, enable_prefix_cache=False)
    handles = [eng.submit(p, max_new_tokens=20) for p in prompts]
    while eng.has_work():
        eng.step()
    assert eng.metrics.as_dict()["counters"]["preemptions"] >= 1
    assert [h.generated for h in handles] == want
    eng.pool.check_leaks()
    eng.pool.reset()
    assert [tuple(a.shape for a in e) for e in eng.pool.layers][0] == (
        (12, BLOCK, 128),)
    h = eng.submit(prompts[0], max_new_tokens=20)
    while eng.has_work():
        eng.step()
    assert h.generated == want[0]


def test_routing_counts_ride_to_the_host_with_the_ids():
    model = _model(8)
    eng = _engine(model)
    h = eng.submit(_tokens(20, 15), max_new_tokens=4)
    while eng.has_work():
        eng.step()
    c = eng.metrics.as_dict()["counters"]
    # two routed layers, four experts a token: 20 prompt tokens in two
    # chunks, then three decode runs of one live slot
    assert c["expert_assignments"] == 2 * 4 * (20 + 3)
    assert c["expert_assignments_decode"] == 2 * 4 * 3
    assert c["experts_read_decode"] == 2 * 4 * 3
    assert 0 < c["experts_read"] <= 2 * 16 * 2 + 2 * 4 * 3
    assert c["prefill_attended_pairs"] == 20 * 21 // 2
    assert eng.decode_cache_size() == eng.prefill_cache_size() == 1


@pytest.mark.parametrize("option,value", [
    ("kv_cache_dtype", "int8"), ("xray_on_start", True),
    ("shardplan", object()), ("mesh", object())])
def test_what_a_latent_model_is_not_served_with_is_refused(option, value):
    with pytest.raises(ValueError, match="latent"):
        _engine(_model(), **{option: value})


def test_speculation_and_sampling_are_refused_for_a_latent_model():
    model = _model()
    with pytest.raises(ValueError, match="latent"):
        _engine(model, speculative=_model(1))
    eng = _engine(model)
    with pytest.raises(ValueError, match="latent"):
        eng.submit(_tokens(8), max_new_tokens=2, temperature=0.8,
                   do_sample=True)
    with pytest.raises(ValueError, match="latent"):
        BlockKVPool(1, 8, 8, 1, 64, kv_cache_dtype="int8", layer_caches=[
            LayerCache(1, 64, jnp.float32, value_dim=48)])


# ------------------------------------------------------------ the layer
def test_shares_of_eight_holders_and_the_shared_expert_counted_once():
    """8 holders of 8 of 64 experts each, the shared expert (which every
    holder would compute alike) counted once: the uncut layer's
    output."""
    def layer(held=None):
        paddle.seed(2)
        return DroplessMoE(32, 16, 64, 4, held=held, scores="sigmoid",
                           selection_bias=True, route_scale=1.8,
                           norm_eps=1e-20)

    whole, shared = layer(), AfmoeMLP(32, 16)
    bias = jnp.asarray(np.random.default_rng(0).normal(size=64) * 0.3,
                       jnp.float32)
    whole.expert_bias._value = bias
    x = jnp.asarray(np.random.default_rng(1).normal(size=(24, 32)),
                    jnp.float32)
    routed, chosen, stats = whole.run(x)
    common = np.asarray(shared.run(x, jnp.ones((32,), jnp.float32), 1e-5),
                        np.float64)
    want = np.asarray(routed, np.float64) + common
    total, read = common.copy(), 0
    for h in range(8):
        held = tuple(range(8 * h, 8 * (h + 1)))
        part = layer(held)
        part.router._value = whole.router._value
        part.expert_bias._value = bias
        for name in ("w_gate", "w_up", "w_down"):
            getattr(part, name)._value = getattr(whole, name)._value[
                jnp.asarray(held)]
        out, chose, st = part.run(x)
        assert (np.asarray(chose) == np.asarray(chosen)).all()
        total += np.asarray(out, np.float64)
        read += int(st.assignments)
    assert read == int(stats.assignments) == 24 * 4
    assert np.abs(total - want).max() < 1e-5 * np.abs(want).max()


def test_the_bias_moves_the_selection_and_not_the_gates():
    rng = np.random.default_rng(3)
    x = rng.normal(size=(12, 32))
    router = rng.normal(size=(32, 16)) / math.sqrt(32)
    loser = 5
    x[:, 0], router[0], router[:, loser] = 1.0, 0.0, 0.0
    router[0, loser] = -6.0
    x, router = jnp.asarray(x, jnp.float32), jnp.asarray(router, jnp.float32)
    scores = 1.0 / (1.0 + np.exp(-np.asarray(x @ router, np.float64)))
    plain, _ = route_topk(x, router, 4, scores="sigmoid", scale=1.8,
                          norm_eps=1e-20)
    assert not (np.asarray(plain) == loser).any()
    bias = jnp.zeros((16,), jnp.float32).at[loser].set(5.0)
    chosen, gates = route_topk(x, router, 4, scores="sigmoid", bias=bias,
                               scale=1.8, norm_eps=1e-20)
    chosen = np.asarray(chosen)
    assert (chosen[:, 0] == loser).all()
    own = np.take_along_axis(scores, chosen, axis=1)
    assert np.abs(np.asarray(gates)
                  - 1.8 * own / own.sum(1, keepdims=True)).max() < 1e-6


# ---------------------------------------------------------- the kernels
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 5e-6),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_latent_kernels_against_plain_attention(dtype, tol, use_pallas):
    rng = np.random.default_rng(0)
    NB, W, VL, H, S, P, T = 40, 256, 128, 5, 3, 8, 32
    pool = jnp.asarray(rng.normal(size=(NB, 16, W)) * 0.5, dtype)
    table = jnp.asarray(rng.permutation(np.arange(1, NB))[:S * P]
                        .reshape(S, P), jnp.int32)
    pos = jnp.asarray([0, 37, 127], jnp.int32)
    q = jnp.asarray(rng.normal(size=(S, H, W)) * 0.3, dtype)
    entry = jnp.asarray(rng.normal(size=(S, W)) * 0.5, dtype)

    def plain(query, keys):
        s = np.asarray(query, np.float64) @ keys.T
        p = np.exp(s - s.max(-1, keepdims=True))
        return (p / p.sum(-1, keepdims=True)) @ keys[:, :VL]

    out, new_pool = fused_latent_decode(
        q, entry, pool, table, pos, value_lanes=VL, use_pallas=use_pallas,
        interpret=True)
    flat = np.asarray(new_pool.astype(jnp.float32), np.float64)
    for b in range(S):
        keys = flat[np.asarray(table[b])].reshape(-1, W)[:int(pos[b]) + 1]
        assert np.array_equal(keys[-1], np.asarray(
            entry[b].astype(jnp.float32), np.float64))
        want = plain(q[b].astype(jnp.float32), keys)
        assert np.abs(np.asarray(out[b]) - want).max() < tol
    qc = jnp.asarray(rng.normal(size=(2, T, H, W)) * 0.3, dtype)
    start = jnp.asarray([0, 64], jnp.int32)
    ctx = fused_latent_chunk(qc, new_pool, table[:2], start, value_lanes=VL,
                             use_pallas=use_pallas, interpret=True)
    assert ctx.shape == (2, T, H, VL) and ctx.dtype == jnp.float32
    for b in range(2):
        keys = flat[np.asarray(table[b])].reshape(-1, W)
        for t in (0, 5, T - 1):
            want = plain(qc[b, t].astype(jnp.float32),
                         keys[:int(start[b]) + t + 1])
            assert np.abs(np.asarray(ctx[b, t]) - want).max() < tol


# ------------------- the models that were served before: as they were
# sha256 of the lowered text of the tiny window model's two step
# programs, taken on the commit before this model came (the Mistral-shaped
# and the block model's are ``test_afmoe_serving.STEP_PROGRAM_SHA256``).
# A change that is MEANT to alter those programs renews the hashes (the
# assertion prints them).  ``afmoe.decode`` was renewed when the decode
# walk took one split-K cell a sequence on a device of one core (the CPU
# among them).
AFMOE_PROGRAM_SHA256 = {
    "afmoe.chunk":
        "da4c1754481fdd03e98e251d542587894f7b84e395d64be054cd296bf42629c9",
    "afmoe.decode":
        "6e39e42f8755a44e45f40a0109632b1a2eddb2614907740a9ad5011ad6998874",
}


def _afmoe_program_texts():
    paddle.seed(0)
    model = AfmoeForCausalLM(AfmoeConfig.tiny())
    model.eval()
    S, nb, C = 2, 8, 16
    eng = Engine(model, ServingConfig(
        max_batch_size=S, block_size=8, num_blocks=16, chunk_tokens=C,
        max_model_len=64))
    table = np.zeros((S, nb), np.int32)
    zeros = np.zeros((S,), np.int32)
    return {
        "afmoe.chunk": make_chunked_prefill_step(model).lower(
            np.zeros((1, C), np.int32), eng.pool.layers,
            (table[:1], table[:1]), zeros[:1], np.int32(0)).as_text(),
        "afmoe.decode": make_paged_decode_step(model).lower(
            np.zeros((S, 1), np.int32), eng.pool.layers, (table, table),
            zeros).as_text()}


def test_the_models_served_before_lower_to_the_text_of_before():
    texts = dict(_step_program_texts(), **_afmoe_program_texts())
    got = {name: hashlib.sha256(text.encode()).hexdigest()
           for name, text in texts.items()}
    assert got == dict(STEP_PROGRAM_SHA256, **AFMOE_PROGRAM_SHA256), got
