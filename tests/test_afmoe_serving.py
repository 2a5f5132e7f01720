"""A model with WINDOW layers beside full ones, gated attention and a
sigmoid-routed mixture of experts with a shared expert, served by the
normal engine from a pool laid out by layer kind (models/afmoe.py,
serving/cache.py, the ``window=`` of the two serving kernels), at tiny
widths on the CPU with seeded random weights, against the plain
reference (``benchmarks/reference/afmoe.py``, the one the benchmark's
``correct`` uses).  Logits are compared, never greedy tokens
(``tests/logit_check.py``)."""
import hashlib
import math

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.kernels.chunked_prefill import fused_chunked_attention
from paddle_tpu.kernels.moe_experts import route_topk
from paddle_tpu.kernels.paged_attention import fused_paged_decode
from paddle_tpu.models import (AfmoeConfig, AfmoeForCausalLM, DroplessMoE,
                               LlamaConfig, LlamaForCausalLM, SDARMoEConfig,
                               SDARMoEForCausalLM)
from paddle_tpu.models.afmoe import AfmoeMLP, routing_witness
from paddle_tpu.models.generation import (make_chunked_prefill_step,
                                          make_paged_block_step,
                                          make_paged_decode_step)
from paddle_tpu.models.llama import (apply_rope, paged_scatter,
                                     precompute_rope)
from paddle_tpu.resilience.chaos import FaultPlan
from paddle_tpu.serving import Engine, ServingConfig
from paddle_tpu.serving.cache import (BlockKVPool, LayerCache, PoolExhausted,
                                      describe_cache)
from paddle_tpu.serving.overload import EngineQuarantined
from benchmarks.reference import afmoe as reference
from logit_check import assert_logits_within

WINDOW = 32


def _model(seed=0, **overrides):
    paddle.seed(seed)
    model = AfmoeForCausalLM(AfmoeConfig.tiny(**overrides))
    model.eval()
    return model


def _cfg(model):
    """The model's settings as a configuration file gives them."""
    c = model.config
    return {k: getattr(c, k) for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "rms_norm_eps", "rope_theta", "sliding_window",
        "num_experts", "num_experts_per_tok", "route_norm", "route_scale",
        "score_func", "mup_enabled")}


def _engine(model, **kw):
    kw = dict(dict(max_batch_size=3, block_size=8, num_blocks=64,
                   chunk_tokens=16, max_model_len=200), **kw)
    return Engine(model, ServingConfig(**kw))


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(1, 250, size=n,
                                                dtype=np.int32)


class _ByHand:
    """Sequences driven through an engine's own step programs, a slot
    each, under tables the engine's own manager lays out
    (``BlockKVPool.advance_window``): what ``Engine.step()`` does for a
    request, with the fed tokens given."""

    def __init__(self, eng):
        cfg = eng.config
        kw = dict(fused=cfg.fused_kernels, kv_cache_dtype=cfg.kv_cache_dtype)
        self.eng, self.pool = eng, eng.pool
        self.chunk = make_chunked_prefill_step(eng.model, **kw)
        self.step = make_paged_decode_step(eng.model, **kw)
        shape = (cfg.max_batch_size, eng.max_blocks_per_seq)
        self.full = np.zeros(shape, np.int32)
        self.window = np.zeros(shape, np.int32)
        self.lengths = np.zeros((cfg.max_batch_size,), np.int32)
        self.owned = {}         # slot -> the full group's blocks
        self.pages = {}         # slot -> {page: window-group block}
        self.most_pages = 0
        self.released = []      # window-group blocks given back, in order

    def _bind(self, pools):
        self.pool.layers = [tuple(entry) for entry in pools]

    def _advance(self, slot, first_query, end):
        owned = self.owned.setdefault(slot, [])
        n = self.pool.blocks_for(end) - len(owned)
        if n > 0:
            new = self.pool.allocate(slot, n)
            self.full[slot, len(owned):len(owned) + n] = new
            owned.extend(new)
        pages = self.pages.setdefault(slot, {})
        before = dict(pages)
        self.pool.advance_window(slot, pages, self.window[slot],
                                 first_query, end)
        self.released += [b for p, b in before.items() if p not in pages]
        self.most_pages = max(self.most_pages, len(pages))

    def prefill(self, slot, prompt):
        """The prompt's chunks; the logits ``[V]`` of its last token."""
        C = self.eng.chunk_tokens
        for start in range(0, len(prompt), C):
            n_tok = min(C, len(prompt) - start)
            self._advance(slot, start, start + n_tok)
            ids = np.zeros((1, C), np.int32)
            ids[0, :n_tok] = prompt[start:start + n_tok]
            (last, _), pools = self.chunk(
                ids, self.pool.layers,
                (self.full[slot:slot + 1].copy(),
                 self.window[slot:slot + 1].copy()),
                np.asarray([start], np.int32), np.int32(n_tok - 1))
            self._bind(pools)
        self.lengths[slot] = len(prompt)
        return np.asarray(last)[0]

    def decode(self, feed):
        """One decode step: ``feed`` maps slot -> token; logits
        ``{slot: [V]}``."""
        tok = np.zeros((len(self.lengths), 1), np.int32)
        for slot, t in feed.items():
            pos = int(self.lengths[slot])
            self._advance(slot, pos, pos + 1)
            tok[slot, 0] = t
        (logits, stats), pools = self.step(
            tok, self.pool.layers, (self.full.copy(), self.window.copy()),
            self.lengths.copy())
        self._bind(pools)
        for slot in feed:
            self.lengths[slot] += 1
        self.stats = np.asarray(stats)
        return {slot: np.asarray(logits)[slot] for slot in feed}

    def row(self, slot, prompt, feed):
        """``[1 + len(feed), V]`` of one sequence alone."""
        out = [self.prefill(slot, prompt)]
        out += [self.decode({slot: t})[slot] for t in feed]
        return np.stack(out)


# ------------------------------------------------- against the reference
@pytest.mark.parametrize("seed", [0, 1])
def test_full_forward_against_the_reference(seed):
    """Every layer under its own mask: three windows of context."""
    model = _model(seed)
    tokens = _tokens(3 * WINDOW + 5, seed)
    got = np.asarray(model(paddle.to_tensor(tokens[None]))._value[0])
    want = np.asarray(reference.logits(
        reference.weights_of(model), _cfg(model), tokens, last=len(tokens)))
    assert_logits_within(got, want, 2e-5, "full forward")


@pytest.mark.parametrize("block,chunk", [(8, 16), (4, 24), (16, 16)])
def test_chunked_prefill_then_decode_against_the_reference(block, chunk):
    """A sequence several windows long through the engine's own chunk
    and decode programs, pages released behind the window as the manager
    releases them, against one full forward of the row with the window
    as a mask; the reference replays what the programs chose, read from
    the sidecar long after the window layers' pages went back."""
    model = _model(2)
    eng = _engine(model, block_size=block, chunk_tokens=chunk)
    prompt, feed = _tokens(100, 3), _tokens(6, 4)
    hand = _ByHand(eng)
    got = hand.row(0, prompt, feed)
    row = np.concatenate([prompt, feed])
    chose = routing_witness(model, eng, row, hand.full[0])
    want, report = reference.logits(
        reference.weights_of(model), _cfg(model), row, last=1 + len(feed),
        witness=chose)
    assert_logits_within(got, np.asarray(want), 2e-5, "chunks then decode")
    routed = sum(1 for layer in model.model.layers if layer.routed)
    assert report["ok"] and report["decisions"] == routed * len(row) * 4
    assert report["not_first_choice"] == 0
    assert hand.released, "the window moved past pages"
    assert hand.most_pages <= eng.pool.window_pages_per_seq \
        == -(-(WINDOW + chunk) // block) + 1


def test_a_window_layer_run_as_a_full_layer_moves_the_logits(monkeypatch):
    """The comparison above is tight enough to see the window: with the
    served window layers run as full ones the same check fails."""
    from paddle_tpu.kernels import chunked_prefill, paged_attention

    model = _model(2)
    chunked = chunked_prefill.fused_chunked_attention
    decode = paged_attention.fused_paged_decode
    monkeypatch.setattr(
        chunked_prefill, "fused_chunked_attention",
        lambda *a, window=None, **kw: chunked(*a, **kw))
    monkeypatch.setattr(
        paged_attention, "fused_paged_decode",
        lambda *a, window=None, **kw: decode(*a, **kw))
    # (every page kept: a full layer's walk would read released ones)
    monkeypatch.setattr(BlockKVPool, "window_first_page",
                        lambda self, pos: 0)
    eng = _engine(model, chunk_tokens=200)
    prompt, feed = _tokens(100, 3), _tokens(2, 4)
    got = _ByHand(eng).row(0, prompt, feed)
    want = reference.logits(
        reference.weights_of(model), _cfg(model),
        np.concatenate([prompt, feed]), last=3)
    with pytest.raises(AssertionError, match="of the largest logit"):
        assert_logits_within(got, np.asarray(want), 1e-3)


# ---------------------------------------------------- the window group
def test_released_pages_are_taken_by_another_sequence():
    """Sequence A's prefill moves its window past pages; sequence B
    takes those very blocks and fills them; no logit of A moves."""
    model = _model(5)
    prompt_a, feed_a = _tokens(90, 6), _tokens(5, 7)
    alone = _ByHand(_engine(model)).row(0, prompt_a, feed_a)

    eng = _engine(model)
    hand = _ByHand(eng)
    got = [hand.prefill(0, prompt_a)]
    got.append(hand.decode({0: feed_a[0]})[0])
    given_back = hand.released[-1]          # by that very decode step
    assert given_back not in hand.pages[0].values()
    hand.prefill(1, _tokens(20, 8))
    assert given_back in hand.pages[1].values(), \
        "B holds the block A gave back"
    for t in feed_a[1:]:
        got.append(hand.decode({0: t, 1: 9})[0])
    assert np.abs(np.stack(got) - alone).max() \
        <= 1e-6 * np.abs(alone).max()
    assert hand.most_pages <= eng.pool.window_pages_per_seq
    # a released page's entry names the garbage block
    first = eng.pool.window_first_page(int(hand.lengths[0]) - 1)
    assert first > 0 and (hand.window[0, :first] == 0).all()
    assert (hand.window[0, first:hand.pool.blocks_for(
        int(hand.lengths[0]))] > 0).all()


def test_the_engine_keeps_every_sequence_under_the_bound():
    """Through ``Engine.step()``: prefill of long prompts and decode,
    three slots; no sequence ever holds more window pages than the
    bound, pages go back while requests run, both groups end empty."""
    model = _model(1)
    eng = _engine(model)
    prompts = [_tokens(n, 20 + n) for n in (120, 37, 70, 9, 101)]
    reqs = [eng.submit(p, max_new_tokens=40) for p in prompts]
    most = 0
    while eng.step():
        most = max([most] + [len(r.window_pages) for r in eng._slots
                             if r is not None])
        assert eng.pool.window.num_used == sum(
            len(r.window_pages) for r in eng._slots if r is not None)
    bound = eng.pool.window_pages_per_seq
    assert 0 < most <= bound == -(-(WINDOW + 16) // 8) + 1
    c = eng.stats()["counters"]
    assert c["window_pages_released"] > 0 and c["preemptions"] == 0
    assert c["window_seq_steps"] > 0
    assert c["window_pages_live"] / c["window_seq_steps"] \
        <= -(-WINDOW // 8) + 1
    assert 0 < c["decode_window_tokens"] < c["decode_context_tokens"]
    assert c["experts_read_decode"] < c["experts_read"]
    assert c["expert_assignments_decode"] < c["expert_assignments"]
    assert all(r.finish_reason == "length" and r.num_generated == 40
               for r in reqs)
    eng.pool.check_leaks()
    assert eng.pool.window.num_used == eng.pool.num_used == 0
    assert eng.decode_cache_size() == 1 and eng.prefill_cache_size() == 1
    stats = eng.stats()["pool"]
    assert stats["window_pages_per_seq"] == bound
    assert stats["window_capacity_blocks"] == 3 * bound


def test_the_engine_s_first_token_and_decode_logits_are_the_reference_s():
    """What ``Engine.step()`` emits is the argmax of logits the
    reference agrees with: every emitted token whose row is decided."""
    from logit_check import decided

    model = _model(3)
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


def test_a_greedy_bucket_fetches_ids_and_routing_counts_alone():
    """A decode step brings 4 bytes a slot and its three routing counts
    to the host, each chunk its three counts, a first token its one id:
    never a row of logits."""
    model = _model(3)
    eng = _engine(model)
    reqs = [eng.submit(_tokens(n, n), max_new_tokens=6)
            for n in (70, 9, 33)]
    eng.run_until_complete()
    assert all(r.num_generated == 6 for r in reqs)
    c = eng.stats()["counters"]
    S, counts = eng.config.max_batch_size, 3 * 4
    assert c["fetched_bytes"] == (
        4 * len(reqs) + c["prefill_chunks_run"] * counts
        + c["decode_iterations"] * (4 * S + counts))
    assert c["fetched_bytes"] < 4 * model.config.vocab_size
    assert eng.decode_cache_size() == 1 and eng.prefill_cache_size() == 1


def test_preemption_and_recompute_past_the_window():
    """A full group too small for both requests: the younger is
    preempted with its context past the window, both groups' pages go
    back, and the recompute path rebuilds both: it ends with the tokens
    of an unpreempted run."""
    model = _model(4)
    prompts = [_tokens(n, 40 + n) for n in (60, 50)]
    # (a model of its own, the same weights: an engine's programs are
    # kept on its model, one per pool shape)
    roomy = _engine(_model(4), max_batch_size=2)
    clean = [roomy.submit(p, max_new_tokens=30) for p in prompts]
    roomy.run_until_complete()
    want = [r.generated for r in clean]
    assert all(r.preemptions == 0 for r in clean)
    eng = _engine(model, max_batch_size=2, num_blocks=20)
    reqs = [eng.submit(p, max_new_tokens=30) for p in prompts]
    eng.run_until_complete()
    assert reqs[1].preemptions >= 1
    for req, tokens in zip(reqs, want):
        assert req.finish_reason == "length" and req.generated == tokens
    eng.pool.check_leaks()
    assert eng.pool.window.num_used == 0
    assert eng.stats()["counters"]["preemptions"] >= 1
    assert eng.decode_cache_size() == 1 and eng.prefill_cache_size() == 1


@pytest.mark.parametrize("group", ["full", "window"])
def test_check_leaks_finds_a_page_leaked_in_either_group(group):
    eng = _engine(_model(0))
    eng.pool.check_leaks()
    leaked = eng.pool if group == "full" else eng.pool.window
    leaked.allocate("lost", 1)
    with pytest.raises(AssertionError,
                       match="leaked window-group blocks"
                       if group == "window" else "leaked blocks"):
        eng.pool.check_leaks()
    eng.pool.free_request("lost")           # the manager frees both
    eng.pool.check_leaks()


def test_the_window_group_says_when_it_is_dry():
    pool = BlockKVPool(
        2, 16, 4, 2, 8, enable_prefix_cache=False, layer_caches=[
            LayerCache(2, 8, jnp.float32, window=8),
            LayerCache(2, 8, jnp.float32)],
        window_blocks=4, window_pages_per_seq=3)
    assert [c.kind for c in pool.layer_caches] == ["window", "full"]
    assert pool.layers[0][0].shape[0] == 4 and pool.layers[1][0].shape[0] == 16
    row, pages = np.zeros((8,), np.int32), {}
    assert pool.advance_window("a", pages, row, 0, 12) == 0
    assert sorted(pages) == [0, 1, 2] and (row[:3] > 0).all()
    with pytest.raises(PoolExhausted):
        pool.advance_window("b", {}, np.zeros((8,), np.int32), 0, 4)
    # the query at 12 sees keys 5..12: page 0 (keys 0..3) goes back
    assert pool.advance_window("a", pages, row, 12, 13) == 1
    assert sorted(pages) == [1, 2, 3] and row[0] == 0
    assert not pool.admission_plan(np.arange(9), extra_tokens=1)[2]
    pool.free_request("a")
    assert pool.admission_plan(np.arange(9), extra_tokens=1)[2]
    with pytest.raises(ValueError, match="no prefix cache"):
        BlockKVPool(1, 8, 4, 2, 8, layer_caches=[
            LayerCache(2, 8, jnp.float32, window=8)], window_blocks=4)


def test_revive_rebuilds_both_groups():
    """A step that fails holding the donated pool: ``revive()`` gives
    both groups fresh pages and the request is recomputed to the tokens
    of a clean run."""
    model = _model(6)
    prompt = _tokens(70, 50)
    clean = _engine(model)
    want = clean.submit(prompt, max_new_tokens=8)
    clean.run_until_complete()
    eng = _engine(model)
    req = eng.submit(prompt, max_new_tokens=8)
    with FaultPlan(fail_after_dispatch_at={7}):
        with pytest.raises(EngineQuarantined, match="took its pool"):
            eng.run_until_complete()
    assert eng.pool.lost()
    eng.revive()
    assert not eng.pool.lost() and eng.pool.window.num_used == 0
    assert (eng._window_tables == 0).all() and req.window_pages == {}
    eng.run_until_complete()
    assert req.finish_reason == "length" and req.preemptions == 1
    assert req.generated == want.generated
    eng.pool.check_leaks()


def test_what_a_window_model_is_not_served_with_is_refused():
    model = _model(0)
    draft = _model(1)
    for kw, what in ((dict(speculative=draft), "speculative decoding"),
                     (dict(kv_cache_dtype="int8"), "a quantized KV cache"),
                     (dict(mesh={"tp": 1}), "a runtime mesh")):
        with pytest.raises(ValueError, match=f"{what} is not supported "
                           "for a model with window layers"):
            _engine(model, **kw)
    eng = _engine(model)
    with pytest.raises(ValueError, match="sampling is not supported yet"):
        eng.submit(_tokens(5, 0), temperature=0.8, seed=1)
    # prefix reuse: no registration, no match, though the default asks
    assert eng.config.enable_prefix_cache and \
        not eng.pool.enable_prefix_cache
    prompt = _tokens(40, 60)
    for _ in range(2):
        eng.submit(prompt, max_new_tokens=2)
        eng.run_until_complete()
    c = eng.stats()["counters"]
    assert c["prefix_cache_hits"] == 0 and c["cached_prompt_tokens"] == 0
    assert eng.stats()["prefix_index"]["indexed_blocks"] == 0


def test_the_model_describes_its_cache_a_record_a_layer():
    model = _model(0)
    records = describe_cache(model)
    assert [r.kind for r in records] == ["window", "window", "full"]
    assert [r.window for r in records] == [WINDOW, WINDOW, None]
    # the dense layer keeps K and V only, a routed layer its witness too
    assert [len(r.sidecars) for r in records] == [0, 1, 1]
    eng = _engine(model)
    assert [len(entry) for entry in eng.pool.layers] == [2, 3, 3]
    window_blocks = eng.pool.window.num_blocks
    assert [entry[0].shape[0] for entry in eng.pool.layers] == [
        window_blocks, window_blocks, eng.pool.num_blocks]
    # a window layer's witness lies with the full group's pages
    assert eng.pool.layers[1][2].shape[0] == eng.pool.num_blocks
    for other in (LlamaForCausalLM(LlamaConfig.tiny()),
                  SDARMoEForCausalLM(SDARMoEConfig.tiny())):
        assert {r.kind for r in describe_cache(other)} == {"full"}


# ------------------------------------------------------------ the layer
EXPERTS, HELD_EACH = 128, 16


def _routed_layer(seed, held=None):
    paddle.seed(seed)
    return DroplessMoE(32, 16, EXPERTS, 8, held=held, scores="sigmoid",
                       selection_bias=True, route_scale=2.5, norm_eps=1e-20)


def test_shares_of_eight_holders_and_the_shared_expert_counted_once():
    """8 holders of 16 of 128 experts each, the shared expert (which
    every holder would compute alike) counted once: the uncut layer's
    output."""
    whole = _routed_layer(2)
    shared = AfmoeMLP(32, 16)
    bias = jnp.asarray(np.random.default_rng(0).normal(size=EXPERTS) * 0.3,
                       jnp.float32)
    whole.expert_bias._value = bias
    x = jnp.asarray(np.random.default_rng(1).normal(size=(24, 32)),
                    jnp.float32)
    norm = jnp.ones((32,), jnp.float32)
    routed, chosen, stats = whole.run(x)
    common = np.asarray(shared.run(x, norm, 1e-5), np.float64)
    want = np.asarray(routed, np.float64) + common
    total, read = common.copy(), 0
    for h in range(EXPERTS // HELD_EACH):
        held = tuple(range(HELD_EACH * h, HELD_EACH * (h + 1)))
        part = _routed_layer(2, held=held)
        part.router._value = whole.router._value
        part.expert_bias._value = bias
        for name in ("w_gate", "w_up", "w_down"):
            getattr(part, name)._value = getattr(whole, name)._value[
                jnp.asarray(held)]
        out, chose, st = part.run(x)
        assert (np.asarray(chose) == np.asarray(chosen)).all()
        total += np.asarray(out, np.float64)
        read += int(st.assignments)
    assert read == int(stats.assignments) == 24 * 8
    assert np.abs(total - want).max() < 1e-5 * np.abs(want).max()


def test_the_bias_moves_the_selection_and_not_the_weights():
    rng = np.random.default_rng(3)
    x = rng.normal(size=(12, 32))
    router = rng.normal(size=(32, 16)) / math.sqrt(32)
    # an expert no token would choose (a constant feature weighs it
    # down), lifted over every other by its bias
    loser = 5
    x[:, 0], router[0], router[:, loser] = 1.0, 0.0, 0.0
    router[0, loser] = -6.0
    x, router = jnp.asarray(x, jnp.float32), jnp.asarray(router, jnp.float32)
    scores = 1.0 / (1.0 + np.exp(-np.asarray(x @ router, np.float64)))
    plain, gates = route_topk(x, router, 4, scores="sigmoid", scale=2.5,
                              norm_eps=1e-20)
    assert not (np.asarray(plain) == loser).any()
    bias = jnp.zeros((16,), jnp.float32).at[loser].set(5.0)
    chosen, biased = route_topk(x, router, 4, scores="sigmoid", bias=bias,
                                scale=2.5, norm_eps=1e-20)
    chosen = np.asarray(chosen)
    assert (chosen[:, 0] == loser).all()
    # its gate is its own score among the chosen scores: the bias is in
    # the selection alone
    own = np.take_along_axis(scores, chosen, axis=1)
    want = 2.5 * own / own.sum(1, keepdims=True)
    assert np.abs(np.asarray(biased) - want).max() < 1e-6
    assert (np.asarray(biased)[:, 0] < np.asarray(gates)[:, 0]).all()
    # the softmax router is as it was
    soft, soft_gates = route_topk(x, router, 4)
    p = np.exp(np.asarray(x @ router, np.float64))
    p /= p.sum(1, keepdims=True)
    top = np.sort(p, axis=1)[:, ::-1][:, :4]
    assert np.abs(np.asarray(soft_gates)
                  - top / top.sum(1, keepdims=True)).max() < 1e-6


def test_leaving_a_mechanism_out_changes_the_logits(monkeypatch):
    """Each of the model's own mechanisms is seen by the comparison
    with the reference: the attention gate, RoPE kept off the full
    layer, the shared expert."""
    model = _model(7)
    tokens = _tokens(70, 8)
    want = np.asarray(reference.logits(
        reference.weights_of(model), _cfg(model), tokens, last=len(tokens)))

    def forward():
        return np.asarray(model(paddle.to_tensor(tokens[None]))._value[0])

    assert_logits_within(forward(), want, 2e-5)
    shared = AfmoeMLP.run
    rotate = AfmoeForCausalLM._rotate
    for name, patch in (
            ("no shared expert", lambda m: m.setattr(
                AfmoeMLP, "run", lambda self, x, nw, eps:
                jnp.zeros_like(x) if self.gate_proj.weight.shape[1] == 32
                else shared(self, x, nw, eps))),
            ("rope on the full layer", lambda m: m.setattr(
                AfmoeForCausalLM, "_rotate",
                lambda self, layer, q, k, start: rotate(
                    self, self.model.layers[0], q, k, start)))):
        with monkeypatch.context() as m:
            patch(m)
            with pytest.raises(AssertionError):
                assert_logits_within(forward(), want, 1e-3, name)
    assert_logits_within(forward(), want, 2e-5)


# ---------------------------------------------------------- the kernels
def _dense_attention(q, keys, values, q_pos, window):
    """``q [H, D]`` at ``q_pos`` over ``keys/values [L, KVH, D]``."""
    lo = 0 if window is None else max(0, q_pos - window + 1)
    out = np.zeros(q.shape)
    rep = q.shape[0] // keys.shape[1]
    for h in range(q.shape[0]):
        s = keys[lo:q_pos + 1, h // rep] @ q[h] / math.sqrt(q.shape[1])
        w = np.exp(s - s.max())
        out[h] = (w / w.sum()) @ values[lo:q_pos + 1, h // rep]
    return out


@pytest.fixture(scope="module")
def paged():
    rng = np.random.default_rng(0)
    B, H, KVH, D, bs, nbs, nb = 3, 4, 2, 16, 8, 16, 64
    pools = [jnp.asarray(rng.normal(size=(nb, bs, KVH, D)), jnp.float32)
             for _ in range(2)]
    table = jnp.asarray(rng.permutation(np.arange(1, nb))[:B * nbs]
                        .reshape(B, nbs), jnp.int32)
    return rng, (B, H, KVH, D), pools, table


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
@pytest.mark.parametrize("window,rope", [(20, True), (20, False),
                                         (None, False), (5, True)])
def test_decode_kernel_with_a_window(paged, window, rope, use_pallas):
    """The walk starts at the window's first page and masks that page's
    earlier keys; ``cos=None`` is a layer with no position encoding."""
    rng, (B, H, KVH, D), (k_pool, v_pool), table = paged
    cos, sin = precompute_rope(D, 256, 10000.0)
    pos = jnp.asarray([5, 37, 100], jnp.int32)
    q, k_new, v_new = (jnp.asarray(rng.normal(size=(B, 1, n, D)),
                                   jnp.float32) for n in (H, KVH, KVH))
    out, new_k, new_v = fused_paged_decode(
        q, k_new, v_new, k_pool, v_pool, table, pos,
        cos if rope else None, sin if rope else None, window=window,
        use_pallas=use_pallas, interpret=True)
    for b in range(B):
        p = int(pos[b])
        at = jnp.asarray([p])
        qr, kr = (np.asarray(apply_rope(t[b:b + 1], cos, sin, at))[0, 0]
                  if rope else np.asarray(t[b, 0]) for t in (q, k_new))
        keys, values = (np.concatenate([np.asarray(pool)[int(i)]
                                        for i in table[b]])
                        for pool in (new_k, new_v))
        assert np.abs(keys[p] - kr).max() < 1e-6
        want = _dense_attention(qr, keys, values, p, window)
        assert np.abs(np.asarray(out)[b, 0] - want).max() < 2e-6


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
@pytest.mark.parametrize("window", [20, 5, 130])
def test_chunk_kernel_with_a_window(paged, window, use_pallas):
    rng, (B, H, KVH, D), (k_pool, v_pool), table = paged
    T = 16
    starts = jnp.asarray([0, 24, 96], jnp.int32)
    q, k, v = (jnp.asarray(rng.normal(size=(B, T, n, D)), jnp.float32)
               for n in (H, KVH, KVH))
    at = starts[:, None] + jnp.arange(T)
    k_pool = paged_scatter(k_pool, k, table, at)
    v_pool = paged_scatter(v_pool, v, table, at)
    out = np.asarray(fused_chunked_attention(
        q, k_pool, v_pool, table, starts, window=window,
        use_pallas=use_pallas, interpret=True))
    for b in range(B):
        keys, values = (np.concatenate([np.asarray(pool)[int(i)]
                                        for i in table[b]])
                        for pool in (k_pool, v_pool))
        for t in range(T):
            want = _dense_attention(np.asarray(q[b, t]), keys, values,
                                    int(starts[b]) + t, window)
            assert np.abs(out[b, t] - want).max() < 2e-6
    with pytest.raises(ValueError, match="causal mask"):
        fused_chunked_attention(q, k_pool, v_pool, table, starts,
                                window=window, mask_block=4)


# ------------------- the models that were served before: as they were
# sha256 of the lowered text of the tiny Mistral-shaped and SDAR step
# programs, taken on the commit before the kernels gained ``window=``
# (``_step_program_texts`` run there under this suite's conftest): a model
# with no window layer traces, through ``window=None``, the programs it
# traced before.  A change that is MEANT to alter those programs renews
# the hashes (the assertion prints them).  ``llama.chunk`` and
# ``llama.decode`` were renewed when the two programs gained the argmax
# that chooses the greedy token beside their logits (ISSUE 35); the block
# model's pair is to the byte what it was: its programs were not touched.
STEP_PROGRAM_SHA256 = {
    "llama.chunk":
        "8ad79cca6afccdcd36ecf6ef8de7a2381fce4446b9b795030ad3fa55549ce525",
    "llama.decode":
        "b1db3eeb575c0aea4b9292232d7b8723f1b04801aceca3688f4bd14972644021",
    "sdar.chunk":
        "3c83e27bbb15e94dda73a11feede617d7e5831daef1a62c3251ae3258327822f",
    "sdar.block":
        "b40815f815eadb14a8e99fd6df58d442028c8cfec5f45317b778088e3adad82a",
}


def _step_program_texts():
    out = {}
    paddle.seed(0)
    llama = LlamaForCausalLM(LlamaConfig.tiny())
    llama.eval()
    paddle.seed(0)
    sdar = SDARMoEForCausalLM(SDARMoEConfig.tiny())
    sdar.eval()
    S, nb, C, L = 2, 8, 16, sdar.config.block_length
    for name, model in (("llama", llama), ("sdar", sdar)):
        eng = Engine(model, ServingConfig(
            max_batch_size=S, block_size=8, num_blocks=16, chunk_tokens=C,
            max_model_len=64))
        table = np.zeros((S, nb), np.int32)
        zeros = np.zeros((S,), np.int32)
        chunk = make_chunked_prefill_step(model)
        out[name + ".chunk"] = chunk.lower(
            np.zeros((1, C), np.int32), eng.pool.layers, table[:1],
            zeros[:1], np.int32(0)).as_text()
        if name == "sdar":
            out[name + ".block"] = make_paged_block_step(model).lower(
                np.zeros((S, L), np.int32), np.zeros((S, L), bool), zeros,
                zeros, zeros, np.zeros((S,), np.float32), eng.pool.layers,
                table).as_text()
        else:
            out[name + ".decode"] = make_paged_decode_step(model).lower(
                np.zeros((S, 1), np.int32), eng.pool.layers, table,
                zeros).as_text()
    return out


def test_window_none_lowers_the_served_programs_to_the_text_of_before():
    got = {name: hashlib.sha256(text.encode()).hexdigest()
           for name, text in _step_program_texts().items()}
    assert got == STEP_PROGRAM_SHA256, got
