"""paddle_tpu.serving — continuous-batching engine, block pool,
scheduler, metrics, endpoint.

The ISSUE 2 done bar lives here: greedy engine outputs are TOKEN-EXACT
with sequential ``generate()`` (including across preemption), the
compiled decode step never retraces after warmup, and the block pool
round-trips every block through a full workload.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (FINISHED, QUEUED, AdmissionError,
                                BlockKVPool, Engine, PoolExhausted,
                                Request, ServingConfig)


# One model for the whole module: every compiled step (prefill per
# bucket, decode per engine config) is cached on it by weights
# fingerprint, so tests share executables instead of recompiling.
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
    """Sequential greedy generate() — the parity oracle."""
    out = model.generate(paddle.to_tensor(prompt[None, :]),
                         temperature=0.0, use_static_cache=True, **kw)
    return np.asarray(out.numpy())[0]


def _config(**kw):
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 64)
    kw.setdefault("max_queue_len", 16)
    return ServingConfig(**kw)


# ---------------------------------------------------------------------------
# BlockKVPool
# ---------------------------------------------------------------------------

class TestBlockKVPool:
    def _pool(self, num_blocks=8, block_size=4):
        return BlockKVPool(num_layers=2, num_blocks=num_blocks,
                           block_size=block_size, kv_heads=2, head_dim=4)

    def test_block0_reserved(self):
        pool = self._pool()
        got = pool.allocate("r", pool.capacity_blocks)
        assert 0 not in got
        assert pool.num_free == 0

    def test_allocate_free_roundtrip(self):
        pool = self._pool()
        a = pool.allocate("a", 3)
        b = pool.allocate("b", 2)
        assert pool.num_used == 5
        assert sorted(pool.owned_by("a")) == sorted(a)
        pool.free_request("a")
        pool.free(b)
        assert pool.num_free == pool.capacity_blocks
        pool.check_leaks()

    def test_double_free_raises(self):
        pool = self._pool()
        blocks = pool.allocate("a", 1)
        pool.free(blocks)
        with pytest.raises(ValueError, match="double free"):
            pool.free(blocks)

    def test_exhaustion_raises_and_keeps_state(self):
        pool = self._pool(num_blocks=4)
        pool.allocate("a", 2)
        with pytest.raises(PoolExhausted):
            pool.allocate("b", 2)
        assert pool.num_free == 1  # failed allocation took nothing

    def test_blocks_for_ceil_division(self):
        pool = self._pool(block_size=4)
        assert [pool.blocks_for(n) for n in (1, 4, 5, 8, 9)] == \
            [1, 1, 2, 2, 3]

    def test_check_leaks_reports_owner(self):
        pool = self._pool()
        pool.allocate("leaky", 1)
        with pytest.raises(AssertionError, match="leaky"):
            pool.check_leaks()


# ---------------------------------------------------------------------------
# Engine: the parity + no-retrace done bar
# ---------------------------------------------------------------------------

class TestEngineParity:
    def test_greedy_parity_mixed_lengths(self, model):
        """Continuous-batched greedy == sequential generate(), token for
        token, across prompt lengths that pad to different buckets."""
        prompts = _prompts([3, 7, 5, 11, 4, 6])
        refs = [_reference(model, p, max_new_tokens=8) for p in prompts]
        eng = Engine(model, _config())
        outs = eng.generate(prompts, max_new_tokens=8)
        for out, ref in zip(outs, refs):
            np.testing.assert_array_equal(out, ref)

    def test_never_retraces_after_warmup(self, model):
        """The compiled decode step holds ONE jit cache entry no matter
        how requests churn through the bucket (the H101 property the
        engine asserts every iteration under strict_no_retrace)."""
        eng = Engine(model, _config())
        eng.generate(_prompts([3, 5]), max_new_tokens=4)
        warm = eng.decode_cache_size()
        eng.generate(_prompts([9, 2, 7], seed=3), max_new_tokens=6)
        assert eng.decode_cache_size() == warm

    def test_no_block_leaks_after_workload(self, model):
        eng = Engine(model, _config())
        eng.generate(_prompts([3, 7, 5, 11, 4]), max_new_tokens=6)
        eng.pool.check_leaks()
        assert eng.pool.num_free == eng.pool.capacity_blocks

    def test_eos_terminates_request(self, model):
        p = _prompts([5])[0]
        ref = _reference(model, p, max_new_tokens=8)
        eos = int(ref[5 + 2])  # third generated token
        ref_eos = _reference(model, p, max_new_tokens=8, eos_token_id=eos)
        eng = Engine(model, _config())
        req = eng.submit(p, max_new_tokens=8, eos_token_id=eos)
        eng.run_until_complete()
        assert req.finish_reason == "eos"
        np.testing.assert_array_equal(req.output_ids(), ref_eos)

    def test_stop_sequence_terminates_request(self, model):
        p = _prompts([4])[0]
        ref = _reference(model, p, max_new_tokens=8)
        stop = [int(ref[4 + 1]), int(ref[4 + 2])]  # generated bigram
        ref_stop = _reference(model, p, max_new_tokens=8,
                              stop_sequences=[stop])
        eng = Engine(model, _config())
        req = eng.submit(p, max_new_tokens=8, stop_sequences=[stop])
        eng.run_until_complete()
        assert req.finish_reason == "stop"
        assert req.generated[-2:] == stop
        np.testing.assert_array_equal(req.output_ids(), ref_stop)

    def test_single_token_request_finishes_at_prefill(self, model):
        eng = Engine(model, _config())
        [out] = eng.generate(_prompts([5]), max_new_tokens=1)
        ref = _reference(model, _prompts([5])[0], max_new_tokens=1)
        np.testing.assert_array_equal(out, ref)
        assert eng.stats()["counters"]["decode_iterations"] == 0


class TestAdmissionControl:
    def test_bounded_queue_rejects(self, model):
        eng = Engine(model, _config(max_queue_len=2))
        for _ in range(2):
            eng.submit(_prompts([3])[0], max_new_tokens=2)
        with pytest.raises(AdmissionError, match="queue full"):
            eng.submit(_prompts([3])[0], max_new_tokens=2)
        assert eng.stats()["counters"]["requests_rejected"] == 1
        eng.run_until_complete()

    def test_impossible_fit_rejected_outright(self, model):
        # capacity 3 blocks * 4 tokens = 12; this request needs 16
        eng = Engine(model, _config(num_blocks=4))
        with pytest.raises(AdmissionError, match="capacity"):
            eng.submit(_prompts([8])[0], max_new_tokens=8)

    def test_max_model_len_enforced(self, model):
        eng = Engine(model, _config())
        with pytest.raises(AdmissionError, match="max_model_len"):
            eng.submit(_prompts([4])[0],
                       max_new_tokens=eng.max_model_len)

    def test_sampling_routed_through_sampling_params(self, model):
        # generate() call-site parity: temperature/do_sample/top_k/top_p
        # route into SamplingParams (ISSUE 19) instead of being rejected;
        # invalid knobs still fail loudly AT SUBMIT, not mid-decode
        eng = Engine(model, _config())
        greedy = eng.submit(_prompts([3])[0], max_new_tokens=2,
                            temperature=0.0)
        assert greedy.sampling is None      # greedy stays off-path
        hot = eng.submit(_prompts([3])[0], max_new_tokens=2,
                         temperature=0.7, top_k=8, seed=1)
        assert hot.sampling.temperature == 0.7 and hot.sampling.top_k == 8
        ds = eng.submit(_prompts([3])[0], max_new_tokens=2,
                        do_sample=True)
        assert ds.sampling.temperature == 1.0   # reference default
        with pytest.raises(ValueError, match="top_p"):
            eng.submit(_prompts([3])[0], max_new_tokens=2,
                       do_sample=True, top_p=0.0)
        with pytest.raises(ValueError, match="temperature"):
            eng.submit(_prompts([3])[0], max_new_tokens=2,
                       sampling={"temperature": -1.0})
        eng.run_until_complete()

    def test_fcfs_completion_order(self, model):
        """One slot: requests retire strictly in arrival order."""
        eng = Engine(model, _config(max_batch_size=1))
        reqs = [eng.submit(p, max_new_tokens=3)
                for p in _prompts([3, 5, 4])]
        done = eng.run_until_complete()
        assert list(done) == [r.request_id for r in reqs]


class TestPreemption:
    def test_preempt_requeue_roundtrip_keeps_parity(self, model):
        """Pool sized so two admitted requests cannot BOTH reach full
        length: the younger is evicted mid-decode, requeued, recomputed
        — and still produces token-exact greedy output."""
        prompts = _prompts([4, 4], seed=7)
        refs = [_reference(model, p, max_new_tokens=10) for p in prompts]
        # capacity 5 blocks * 4 = 20 token-positions; each request needs
        # ceil((4+10)/4)=4 blocks at full length but only 2 to admit, so
        # both admit and later collide on the 5th block.
        eng = Engine(model, _config(max_batch_size=2, num_blocks=6))
        reqs = [eng.submit(p, max_new_tokens=10) for p in prompts]
        eng.run_until_complete()
        for req, ref in zip(reqs, refs):
            np.testing.assert_array_equal(req.output_ids(), ref)
        st = eng.stats()
        assert st["counters"]["preemptions"] >= 1
        # FCFS fairness: the YOUNGER request is the victim
        assert reqs[1].preemptions >= 1 and reqs[0].preemptions == 0
        assert st["requests"][reqs[1].request_id]["preemptions"] >= 1
        eng.pool.check_leaks()

    def test_victim_is_youngest_and_head_of_queue(self, model):
        from paddle_tpu.serving.scheduler import Scheduler

        pool = BlockKVPool(2, 8, 4, 2, 4)
        sched = Scheduler(pool)
        a = Request(prompt=np.ones(4, np.int32), max_new_tokens=2)
        b = Request(prompt=np.ones(4, np.int32), max_new_tokens=2)
        sched.running = [a, b]
        assert sched.pick_victim() is b
        b.generated = [1, 2]
        sched.requeue_preempted(b)
        assert sched.waiting[0] is b
        assert b.generated == [] and b.blocks == []
        # re-admission keeps the original FCFS ordinal
        assert b.ordinal > a.ordinal


class TestMetrics:
    def test_request_timings_and_counters(self, model):
        eng = Engine(model, _config())
        reqs = [eng.submit(p, max_new_tokens=4) for p in _prompts([3, 6])]
        eng.run_until_complete()
        st = eng.stats()
        c = st["counters"]
        assert c["requests_submitted"] == 2
        assert c["requests_completed"] == 2
        assert c["prefills"] == 2
        assert c["tokens_generated"] == sum(r.num_generated for r in reqs)
        assert c["decode_iterations"] >= 3
        for req in reqs:
            t = st["requests"][req.request_id]
            assert t["ttft_s"] is not None and t["ttft_s"] >= 0
            assert t["tpot_s"] is not None and t["tpot_s"] >= 0
            assert t["queue_time_s"] >= 0
            assert t["e2e_s"] >= t["ttft_s"]
            assert t["tokens_generated"] == 4
            assert t["finish_reason"] == "length"
        g = st["gauges"]
        assert 0 < g["batch_occupancy_avg"] <= 1
        assert 0 <= g["cache_utilization_avg"] <= 1

    def test_stats_contract_for_router(self, model):
        """The load/affinity signals the fleet router places by are part
        of the ``stats()`` contract: ``pending_prefill_tokens`` (exact
        backlog token count) and ``prefix_index`` (the pool's prefix-
        cache summary in hex)."""
        eng = Engine(model, _config())
        eng.submit(_prompts([6, 9], seed=3)[0], max_new_tokens=2)
        eng.submit(_prompts([6, 9], seed=3)[1], max_new_tokens=2)
        st = eng.stats()
        assert st["queue_depth"] == 2
        assert st["pending_prefill_tokens"] == 15       # 6 + 9, untouched
        assert st["pending_prefill_tokens"] == eng.pending_prefill_tokens()
        eng.run_until_complete()
        st = eng.stats()
        assert st["pending_prefill_tokens"] == 0
        idx = st["prefix_index"]
        assert idx["block_size"] == eng.config.block_size
        assert idx["indexed_blocks"] >= 1               # prompts registered
        assert idx["cached_blocks"] >= 0
        hashes = idx["hashes"]
        assert hashes and len(hashes) == idx["indexed_blocks"]
        for h in hashes + idx["roots"]:
            int(h, 16)                                  # hex digests
            assert len(h) == 32                         # blake2b-128
        assert set(idx["roots"]) <= set(hashes)


class TestEndpoint:
    def test_predictor_parity_handles(self, model):
        from paddle_tpu.inference import create_serving_endpoint

        ep = create_serving_endpoint(model, _config(), max_new_tokens=4)
        assert ep.get_input_names() == ["input_0"]
        prompts = np.stack(_prompts([5, 5]))
        ep.get_input_handle("input_0").copy_from_cpu(prompts)
        outs = ep.run()
        rect = ep.get_output_handle("output_0").copy_to_cpu()
        assert rect.shape == (2, 9)
        for i, p in enumerate(prompts):
            ref = _reference(model, p, max_new_tokens=4)
            np.testing.assert_array_equal(outs[i], ref)
            np.testing.assert_array_equal(rect[i], ref)

    def test_streaming_submit_poll_result(self, model):
        from paddle_tpu.serving import Endpoint

        ep = Endpoint(model, _config(), max_new_tokens=3)
        req = ep.submit(_prompts([4])[0])
        assert ep.result(req) is None and req.state == QUEUED
        while ep.poll():
            pass
        assert req.state == FINISHED
        ref = _reference(model, _prompts([4])[0], max_new_tokens=3)
        np.testing.assert_array_equal(ep.result(req), ref)


# ---------------------------------------------------------------------------
# the continuous-batching win (slow: wall-clock-free, but extra decodes)
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestThroughput:
    def test_staggered_workload_fewer_decode_iterations(self, model):
        """8 staggered requests: the engine interleaves them in one
        bucket, so TOTAL decode iterations stay well under the
        sequential sum — the continuous-batching claim, measured in
        iterations (deterministic) instead of wall clock (flaky)."""
        prompts = _prompts([3, 5, 4, 6, 3, 7, 5, 4], seed=11)
        max_new = 8
        eng = Engine(model, _config(max_batch_size=8, num_blocks=128))
        reqs = []
        for i, p in enumerate(prompts):
            reqs.append(eng.submit(p, max_new_tokens=max_new))
            eng.step()   # requests arrive WHILE others are decoding
        eng.run_until_complete()
        refs = [_reference(model, p, max_new_tokens=max_new)
                for p in prompts]
        for req, ref in zip(reqs, refs):
            np.testing.assert_array_equal(req.output_ids(), ref)
        engine_iters = eng.stats()["counters"]["decode_iterations"]
        # sequential: each request alone pays max_new - 1 decode steps
        sequential_iters = len(prompts) * (max_new - 1)
        assert engine_iters < sequential_iters, \
            (engine_iters, sequential_iters)


# ---------------------------------------------------------------------------
# Prefix cache: pool semantics (refcounts, chain hashing, LRU, CoW)
# ---------------------------------------------------------------------------

class TestPrefixCachePool:
    def _pool(self, num_blocks=8, block_size=4, **kw):
        return BlockKVPool(num_layers=2, num_blocks=num_blocks,
                           block_size=block_size, kv_heads=2, head_dim=4,
                           **kw)

    def test_free_request_unowned_is_noop(self):
        """Retire paths call free_request unconditionally — a request
        that never got blocks (queued timeout, failed prefill) must not
        blow up."""
        pool = self._pool()
        pool.free_request("never-admitted")   # no raise
        a = pool.allocate("a", 2)
        pool.free_request("a")
        pool.free_request("a")                # second call: also a no-op
        assert pool.num_free == pool.capacity_blocks
        assert 0 not in a

    def test_double_free_message_lists_owners(self):
        pool = self._pool()
        blocks = pool.allocate("alice", 1)
        pool.acquire("bob", blocks)
        with pytest.raises(ValueError, match="double free.*'carol'.*"
                                             "alice.*bob"):
            pool.free(blocks, request_id="carol")
        with pytest.raises(ValueError, match="no current owner"):
            pool.free([pool._free[-1]])

    def test_refcount_shared_block_survives_one_owner(self):
        pool = self._pool()
        blocks = pool.allocate("a", 2)
        pool.acquire("b", blocks)
        assert all(pool.refcount(b) == 2 for b in blocks)
        pool.free_request("a")
        # b still holds them: nothing came back to the free list
        assert pool.num_used == 2
        assert sorted(pool.owned_by("b")) == sorted(blocks)
        pool.free_request("b")
        assert pool.num_free == pool.capacity_blocks
        pool.check_leaks()

    def test_chain_hash_match_semantics(self):
        """Matching is chained: block i matches only when the WHOLE
        prefix through block i matches, full blocks only, stopping at
        the first divergence."""
        pool = self._pool(num_blocks=16)
        toks = np.arange(1, 13, dtype=np.int32)          # 3 full blocks
        blocks = pool.allocate("a", 3)
        pool.register_prefix("a", toks, blocks)
        assert pool.match_prefix(toks) == blocks
        assert pool.match_prefix(toks[:8]) == blocks[:2]
        assert pool.match_prefix(toks[:7]) == blocks[:1]  # partial tail
        # same 2nd block content after a DIFFERENT first block: no match
        # past the divergence (the chain encodes the whole prefix)
        other = toks.copy()
        other[0] = 99
        assert pool.match_prefix(other) == []
        pool.free_request("a")
        assert pool.match_prefix(toks) == blocks          # parked, still hot

    def test_lru_eviction_never_touches_referenced_blocks(self):
        """Under pressure the pool evicts ONLY unreferenced cached
        blocks, oldest-parked first; live requests' blocks are
        untouchable."""
        pool = self._pool(num_blocks=6)
        t1 = np.arange(1, 5, dtype=np.int32)
        t2 = np.arange(11, 15, dtype=np.int32)
        b1 = pool.allocate("a", 1)
        pool.register_prefix("a", t1, b1)
        b2 = pool.allocate("b", 1)
        pool.register_prefix("b", t2, b2)
        pool.free_request("a")        # parked first -> LRU victim
        pool.free_request("b")
        live = pool.allocate("live", 3)   # 3 truly-free blocks left
        assert pool.num_cached == 2 and pool.evictions == 0
        got = pool.allocate("live", 2)    # forces 2 evictions
        assert pool.evictions == 2
        assert set(got) == {b1[0], b2[0]}  # recycled cached blocks
        assert pool.match_prefix(t1) == [] and pool.match_prefix(t2) == []
        # live blocks never appeared as victims
        assert sorted(pool.owned_by("live")) == sorted(live + got)
        with pytest.raises(PoolExhausted):
            pool.allocate("live", 1)
        pool.free_request("live")
        pool.check_leaks()

    def test_cow_shared_and_registered_blocks(self):
        pool = self._pool()
        toks = np.arange(1, 5, dtype=np.int32)
        b = pool.allocate("a", 1)
        # exclusive + unregistered: in-place, no copy
        assert pool.ensure_writable("a", b[0]) == b[0]
        pool.register_prefix("a", toks, b)
        # registered (immutable) even while exclusively owned: copy
        nb = pool.ensure_writable("a", b[0])
        assert nb != b[0] and pool.cow_copies == 1
        assert pool.owned_by("a") == [nb]
        # the registered original stays matchable (parked in the LRU)
        assert pool.match_prefix(toks) == b
        pool.acquire("b2", pool.match_prefix(toks))
        nb2 = pool.ensure_writable("b2", b[0])   # shared again: copy
        assert nb2 not in (b[0], nb) and pool.cow_copies == 2
        pool.free_request("a")
        pool.free_request("b2")
        pool.check_leaks()

    def test_acquire_revives_parked_block(self):
        pool = self._pool()
        toks = np.arange(1, 5, dtype=np.int32)
        b = pool.allocate("a", 1)
        pool.register_prefix("a", toks, b)
        pool.free_request("a")
        assert pool.num_cached == 1
        pool.acquire("b", b)
        assert pool.num_cached == 0 and pool.refcount(b[0]) == 1
        pool.free_request("b")
        pool.check_leaks()

    def test_disabled_cache_never_matches_or_parks(self):
        pool = self._pool(enable_prefix_cache=False)
        toks = np.arange(1, 5, dtype=np.int32)
        b = pool.allocate("a", 1)
        assert pool.register_prefix("a", toks, b) == 0
        assert pool.match_prefix(toks) == []
        pool.free_request("a")
        assert pool.num_cached == 0
        assert pool.num_free == pool.capacity_blocks


# ---------------------------------------------------------------------------
# Prefix cache + chunked prefill: engine-level done bar
# ---------------------------------------------------------------------------

class TestChunkedPrefill:
    def test_cache_on_off_token_identical(self, model):
        """ISSUE 5 parity obligation: greedy output is token-identical
        with prefix cache + chunked prefill enabled vs disabled, and
        both match sequential generate()."""
        shared = _prompts([16], seed=21)[0]
        tails = _prompts([3, 5, 2], seed=22)
        prompts = [np.concatenate([shared, t]) for t in tails]
        refs = [_reference(model, p, max_new_tokens=6) for p in prompts]
        outs = {}
        for enable in (False, True):
            eng = Engine(model, _config(chunk_tokens=8,
                                        enable_prefix_cache=enable))
            outs[enable] = []
            for p in prompts:       # sequential: later ones hit the cache
                req = eng.submit(p, max_new_tokens=6)
                eng.run_until_complete()
                outs[enable].append(req.output_ids())
            eng.pool.check_leaks()
            if enable:
                c = eng.metrics.as_dict()["counters"]
                assert c["prefix_cache_hits"] == 2
                assert c["prefix_cache_misses"] == 1
        for off, on, ref in zip(outs[False], outs[True], refs):
            np.testing.assert_array_equal(off, on)
            np.testing.assert_array_equal(on, ref)

    def test_full_prompt_hit_recomputes_last_token(self, model):
        """Submitting the SAME prompt twice: the second admission may
        reuse every full block, but must still recompute >= 1 token to
        produce first-token logits — via a copy-on-write block, so the
        cached original is never mutated."""
        p = _prompts([8], seed=23)[0]      # exact multiple of block_size
        ref = _reference(model, p, max_new_tokens=5)
        eng = Engine(model, _config(chunk_tokens=8))
        for _ in range(2):
            req = eng.submit(p, max_new_tokens=5)
            eng.run_until_complete()
            np.testing.assert_array_equal(req.output_ids(), ref)
        assert eng.metrics.prefix_cache_hits == 1
        assert eng.pool.cow_copies >= 1
        # the second request prefilled ONE 1-token chunk, not the prompt
        assert req.cached_tokens == p.size - 1
        eng.pool.check_leaks()

    def test_constant_prefill_programs_across_lengths(self):
        """ISSUE 5 acceptance: >= 4 distinct prompt lengths, ONE
        compiled prefill program (the fixed-chunk shape), measured via
        the compile tracker — the bucketed prefill would have compiled
        one per length bucket."""
        paddle.seed(0)
        fresh = LlamaForCausalLM(LlamaConfig.tiny())
        fresh.eval()
        eng = Engine(fresh, _config(chunk_tokens=4))
        prompts = _prompts([3, 7, 11, 14, 6], seed=24)
        refs = [_reference(fresh, p, max_new_tokens=4) for p in prompts]
        outs = eng.generate(prompts, max_new_tokens=4)
        for out, ref in zip(outs, refs):
            np.testing.assert_array_equal(out, ref)
        assert eng._prefill_step.compiles == 1, \
            eng._prefill_step.compiles
        assert eng.prefill_cache_size() == 1
        assert eng._prefill_step.retraces == 0
        # multi-chunk accounting: ceil(L/4) chunks per prompt
        assert eng.metrics.prefill_chunks == sum(
            -(-p.size // 4) for p in prompts)

    def test_eviction_under_pressure_keeps_parity(self, model):
        """Tiny pool + repeated prompts: LRU evictions and preemptions
        churn the cache, yet every output stays token-exact and no
        live-referenced block is ever handed out twice (the leak check
        would catch a double-owned block)."""
        prompts = _prompts([4, 4, 8, 4], seed=7)
        prompts.append(prompts[0].copy())    # full-hit after churn
        refs = [_reference(model, p, max_new_tokens=10) for p in prompts]
        eng = Engine(model, _config(max_batch_size=3, num_blocks=7,
                                    chunk_tokens=8))
        outs = eng.generate(prompts, max_new_tokens=10)
        for out, ref in zip(outs, refs):
            np.testing.assert_array_equal(out, ref)
        assert eng.pool.evictions > 0        # pressure was real
        assert eng.metrics.preempted > 0
        eng.pool.check_leaks()
        assert eng.pool.num_free == eng.pool.capacity_blocks

    def test_preempted_request_reuses_its_own_prefix(self, model):
        """A preempted request's registered prompt blocks survive in
        the LRU; its re-admission is a prefix-cache hit and the rerun
        stays token-exact (recompute mode + cache reuse compose)."""
        prompts = _prompts([8, 8], seed=25)
        refs = [_reference(model, p, max_new_tokens=10) for p in prompts]
        # capacity 6: both prefill (4 blocks), decode growth preempts
        # the younger request, and the survivor finishes with 5 blocks —
        # evicting the victim's parked TAIL but leaving its chain head
        # for the re-admission to hit (leaf-first eviction order)
        eng = Engine(model, _config(max_batch_size=2, num_blocks=7,
                                    chunk_tokens=8))
        outs = eng.generate(prompts, max_new_tokens=10)
        for out, ref in zip(outs, refs):
            np.testing.assert_array_equal(out, ref)
        assert eng.metrics.preempted > 0
        assert eng.metrics.prefix_cache_hits > 0
        eng.pool.check_leaks()

    def test_long_prompt_interleaves_with_decode(self, model):
        """Sarathi-style budget: while a long prompt prefills chunk by
        chunk, an already-running request keeps producing tokens every
        iteration (no prefill stall), and both finish token-exact."""
        short, long_ = _prompts([4, 40], seed=26)
        refs = [_reference(model, p, max_new_tokens=8)
                for p in (short, long_)]
        eng = Engine(model, _config(chunk_tokens=8))
        r_short = eng.submit(short, max_new_tokens=8)
        eng.step()                      # short is admitted + running
        gen_before = r_short.num_generated
        r_long = eng.submit(long_, max_new_tokens=8)
        steps = 0
        while r_long.state != FINISHED and r_short.state != FINISHED:
            eng.step()
            steps += 1
        # the short request advanced during the long prompt's prefill
        assert r_short.num_generated > gen_before
        eng.run_until_complete()
        np.testing.assert_array_equal(r_short.output_ids(), refs[0])
        np.testing.assert_array_equal(r_long.output_ids(), refs[1])
        assert r_long.prefill_chunks == 5    # ceil(40 / 8)
        eng.pool.check_leaks()


# ---------------------------------------------------------------------------
# the greedy lane: the token is chosen where its logits are (ISSUE 35)
# ---------------------------------------------------------------------------

# what the engine served for these prompts while it fetched the logits
# and took ``np.argmax`` on the host (the commit before ISSUE 35, this
# suite's conftest, tiny Llama of seed 0)
_GREEDY_LENGTHS = (3, 21, 9, 34, 5, 17)
_GREEDY_SERVED_BEFORE = (
    (69, 243, 4, 85, 66, 217, 109, 56),
    (174, 104, 87, 201, 64, 62, 135, 20),
    (21, 230, 174, 195, 206, 17, 135, 76),
    (194, 40, 45, 21, 230, 174, 44, 157),
    (200, 217, 109, 209, 4, 85, 176, 44),
    (39, 50, 17, 137, 243, 199, 231, 73),
)


class TestGreedyLane:
    def _serve(self, model, **kw):
        eng = Engine(model, _config(chunk_tokens=16, **kw))
        prompts = _prompts(_GREEDY_LENGTHS, seed=35)
        outs = eng.generate(prompts, max_new_tokens=8)
        return eng, prompts, [o[len(p):] for p, o in zip(prompts, outs)]

    def test_serves_the_tokens_it_served_before(self, model):
        _, _, served = self._serve(model)
        assert [tuple(int(t) for t in row) for row in served] == \
            list(_GREEDY_SERVED_BEFORE)

    @pytest.mark.parametrize("kv_cache_dtype", [None, "int8"])
    def test_a_served_token_is_the_argmax_of_the_public_steps_logits(
            self, model, kv_cache_dtype):
        """The rule of before, replayed: every sequence teacher-forced
        through the public step programs, whose logits a caller still
        gets, and ``np.argmax`` on the host; wherever the best logit
        leads by more than rounding the served token is that one."""
        from logit_check import causal_engine_logits, decided

        eng, prompts, served = self._serve(model,
                                           kv_cache_dtype=kv_cache_dtype)
        rows = judged = 0
        for prompt, tokens in zip(prompts, served):
            logits = causal_engine_logits(eng, prompt, tokens[:-1])
            sure = decided(logits, 1e-5)
            np.testing.assert_array_equal(
                np.argmax(logits, axis=-1)[sure], tokens[sure])
            rows, judged = rows + len(tokens), judged + int(sure.sum())
        assert judged >= 0.9 * rows, (judged, rows)

    def test_a_greedy_bucket_fetches_four_bytes_a_slot(self, model):
        eng, prompts, _ = self._serve(model)
        S, c = eng.config.max_batch_size, eng.stats()["counters"]
        assert c["decode_iterations"] > 0
        # a first token is the chunk's one id, a decode step the bucket's
        assert c["fetched_bytes"] == \
            4 * len(prompts) + c["decode_iterations"] * S * 4

    def test_a_sampled_first_token_still_reads_its_logits_row(self, model):
        from paddle_tpu.serving import SamplingParams

        eng = Engine(model, _config(chunk_tokens=16))
        prompt = _prompts((9,), seed=36)[0]
        req = eng.submit(prompt, max_new_tokens=4,
                         sampling=SamplingParams(temperature=0.8, seed=3))
        eng.run_until_complete()
        assert req.num_generated == 4
        S, c = eng.config.max_batch_size, eng.stats()["counters"]
        V = model.config.vocab_size
        # the sampled decode program has always returned ids
        assert c["fetched_bytes"] == \
            4 * V + c["decode_iterations"] * S * 4
        # ... through the one chunk program the greedy lane reads
        greedy = eng.submit(prompt, max_new_tokens=1)
        eng.run_until_complete()
        assert greedy.num_generated == 1
        assert eng.stats()["counters"]["fetched_bytes"] == \
            c["fetched_bytes"] + 4
        # (the module's model is shared: count what THIS engine compiled)
        assert eng._prefill_step.compiles <= 1
        assert eng._prefill_step.retraces == 0
