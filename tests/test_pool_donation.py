"""Every program that takes a paged KV pool and returns it takes it as a
DONATED argument (``models/generation.py::cached_step(..., donate=)``,
``serving/cache.py::_copy_block_impl``): its output pool aliases its
input, the scatter writes in place, and the arrays handed in are gone
after the call.  So a pool is made of buffers that can each be donated,
and a caller binds what a step returns."""
import warnings

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM, SDARMoEConfig,
                               SDARMoEForCausalLM)
from paddle_tpu.models import generation as gen
from paddle_tpu.serving import Engine, ServingConfig
from paddle_tpu.serving.cache import BlockKVPool
from paddle_tpu.serving.sampling import make_sampled_decode_step
from paddle_tpu.serving.speculative import (make_draft_propose_step,
                                            make_spec_verify_step)

K_DRAFT = 2


def _llama():
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny())
    model.eval()
    return model


def _sdar():
    paddle.seed(0)
    model = SDARMoEForCausalLM(SDARMoEConfig.tiny())
    model.eval()
    return model


def _engine(model, **kw):
    return Engine(model, ServingConfig(max_batch_size=2, block_size=8,
                                       num_blocks=16, **kw))


def _table(eng):
    table = np.zeros((eng.config.max_batch_size, eng.max_blocks_per_seq),
                     np.int32)
    table[0, :2] = (1, 2)
    return table


def _lengths(eng):
    lengths = np.zeros((eng.config.max_batch_size,), np.int32)
    lengths[0] = 5
    return lengths


def _sampling(eng):
    S = eng.config.max_batch_size
    return (np.zeros((S,), np.float32), np.zeros((S,), np.int32),
            np.ones((S,), np.float32), np.zeros((S, 2), np.uint32),
            np.zeros((S,), np.int32))


def _decode_args(eng):
    S = eng.config.max_batch_size
    return (np.ones((S, 1), np.int32), eng.pool.layers, _table(eng),
            _lengths(eng))


def _chunk_args(eng):
    ids = np.zeros((1, eng.chunk_tokens), np.int32)
    ids[0, :8] = np.arange(1, 9)
    return (ids, eng.pool.layers, _table(eng)[:1], np.zeros((1,), np.int32),
            np.int32(7))


def _block_args(eng):
    S, L = eng.config.max_batch_size, eng.block.block_length
    ids = np.full((S, L), 7, np.int32)
    masked = np.zeros((S, L), bool)
    masked[0, L // 2:] = True
    zeros = np.zeros((S,), np.int32)
    mode, n = zeros.copy(), zeros.copy()
    mode[0], n[0] = 1, 1
    return (ids, masked, zeros, mode, n, np.full((S,), 2.0, np.float32),
            eng.pool.layers, _table(eng))


def _verify_args(eng):
    S, V = eng.config.max_batch_size, eng.model.config.vocab_size
    return (np.ones((S,), np.int32), np.ones((S, K_DRAFT), np.int32),
            np.full((S, K_DRAFT, V), 1.0 / V, np.float32), eng.pool.layers,
            _table(eng), _lengths(eng)) + _sampling(eng)


def _kv(dtype):
    return lambda m, fused: dict(fused=fused, kv_cache_dtype=dtype)


# kind -> (model, the factory's arguments given the engine's mode, the
# factory, the step's arguments, where the pool is among them, the
# engine's options, leaves a pool entry has)
KINDS = {
    "decode": (_llama, _kv(None), gen.make_paged_decode_step,
               _decode_args, 1, {}, 2),
    "chunk": (_llama, _kv(None), gen.make_chunked_prefill_step,
              _chunk_args, 1, {}, 2),
    "decode_int8": (_llama, _kv("int8"), gen.make_paged_decode_step,
                    _decode_args, 1, dict(kv_cache_dtype="int8"), 4),
    "chunk_int8": (_llama, _kv("int8"), gen.make_chunked_prefill_step,
                   _chunk_args, 1, dict(kv_cache_dtype="int8"), 4),
    "decode_fp8": (_llama, _kv("fp8"), gen.make_paged_decode_step,
                   _decode_args, 1, dict(kv_cache_dtype="fp8"), 4),
    "decode_gather": (_llama, _kv(None), gen.make_paged_decode_step,
                      _decode_args, 1, dict(fused_kernels=False), 2),
    "sampled": (_llama, _kv(None), make_sampled_decode_step,
                lambda eng: _decode_args(eng) + _sampling(eng), 1, {}, 2),
    "draft": (_llama, lambda m, fused: dict(num_draft=K_DRAFT, fused=fused),
              make_draft_propose_step,
              lambda eng: _decode_args(eng) + _sampling(eng), 1, {}, 2),
    "verify": (_llama, lambda m, fused: dict(num_draft=K_DRAFT, fused=fused),
               make_spec_verify_step, _verify_args, 3, {}, 2),
    # a block model's pool entries hold a sidecar (the routing witness)
    "block": (_sdar, lambda m, fused: dict(fused=fused),
              gen.make_paged_block_step, _block_args, 6, {}, 3),
    "block_chunk": (_sdar, lambda m, fused: dict(fused=fused),
                    gen.make_chunked_prefill_step, _chunk_args, 1, {}, 3),
}


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_a_step_consumes_its_pool_and_aliases_it_to_its_output(kind):
    model_of, factory_kw, make, args_of, at, engine_kw, arity = KINDS[kind]
    model = model_of()
    eng = _engine(model, **engine_kw)
    step = make(model, **factory_kw(model, eng.config.fused_kernels))
    args = args_of(eng)
    assert args[at] is eng.pool.layers
    handed_in = [a for entry in args[at] for a in entry]
    assert len(handed_in) == arity * model.config.num_hidden_layers
    # the program: every pool leaf is an input that one output aliases
    text = step.lower(*args).as_text()
    assert text.count("tf.aliasing_output") == len(handed_in)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        out = step(*args)
    assert not [w for w in caught if "donated" in str(w.message)]
    jax.block_until_ready(out)
    # the call: what was handed in is gone, what came back is whole
    assert all(a.is_deleted() for a in handed_in)
    assert eng.pool.lost()
    eng.pool.layers = [tuple(entry) for entry in out[-1]]
    assert not eng.pool.lost()
    returned = [a for entry in eng.pool.layers for a in entry]
    assert [(a.shape, a.dtype) for a in returned] == \
        [(a.shape, a.dtype) for a in handed_in]
    # and the engine serves on from the pool it was given back
    if eng.block is None:
        prompt = np.arange(1, 8, dtype=np.int32)
        got = eng.generate([prompt], max_new_tokens=3)[0]
        if not engine_kw.get("kv_cache_dtype"):
            ref = model.generate(paddle.to_tensor(prompt[None, :]),
                                 max_new_tokens=3, temperature=0.0)
            np.testing.assert_array_equal(got, np.asarray(ref.numpy())[0])
        eng.pool.check_leaks()


@pytest.mark.parametrize("pool_kw, arity", [
    ({}, 2),
    (dict(kv_cache_dtype="int8"), 4),
    (dict(kv_cache_dtype="fp8"), 4),
    (dict(sidecars=[((3,), np.int32), ((), np.float32)]), 4),
    (dict(kv_cache_dtype="int8", sidecars=[((2,), np.int32)]), 5),
])
def test_a_fresh_pool_has_no_two_leaves_that_are_one_buffer(pool_kw, arity):
    """The runtime refuses to donate one buffer twice."""
    pool = BlockKVPool(3, 8, 4, 2, 16, np.float32, **pool_kw)
    leaves = [a for entry in pool.layers for a in entry]
    assert len(leaves) == 3 * arity
    assert len({a.unsafe_buffer_pointer() for a in leaves}) == len(leaves)
    if "sidecars" in pool_kw:               # a row a block
        assert leaves[-1].shape[0] == 8 and leaves[-1].ndim == 2
    assert not pool.lost()
    # and ``reset`` builds the same pool again, with an empty index
    before = [(a.shape, a.dtype) for a in leaves]
    pool.allocate("r", 2)
    pool.register_prefix("r", np.arange(8), pool.owned_by("r"))
    pool.free_request("r")
    assert pool.num_cached == 2
    pool.reset()
    fresh = [a for entry in pool.layers for a in entry]
    assert [(a.shape, a.dtype) for a in fresh] == before
    assert not set(map(id, fresh)) & set(map(id, leaves))
    assert pool.num_cached == 0 and pool.num_free == pool.capacity_blocks
    assert pool.match_prefix(np.arange(8)) == []


def test_reset_refuses_a_pool_some_request_still_references():
    pool = BlockKVPool(1, 8, 4, 2, 16, np.float32)
    pool.allocate("r", 1)
    with pytest.raises(AssertionError, match="leaked"):
        pool.reset()


@pytest.mark.parametrize("pool_kw", [
    {}, dict(kv_cache_dtype="int8"),
    dict(sidecars=[((3,), np.int32)])])
def test_a_copy_on_write_leaves_one_live_pool(pool_kw):
    pool = BlockKVPool(2, 8, 4, 2, 16, np.float32, **pool_kw)
    rng = np.random.default_rng(0)
    # (``+ 0``: a buffer the runtime owns, as a step's output is; the
    # CPU backend may borrow a host array's memory, which it cannot give)
    pool.layers = [tuple(jax.numpy.asarray(
        rng.integers(1, 100, size=a.shape).astype(a.dtype)) + 0
        for a in entry) for entry in pool.layers]
    want = [[np.array(a) for a in entry] for entry in pool.layers]
    before = [a for entry in pool.layers for a in entry]
    (block,) = pool.allocate("r", 1)
    pool.register_prefix("r", np.arange(4), [block])   # now immutable
    copy = pool.ensure_writable("r", block)
    assert copy != block and pool.cow_copies == 1
    assert all(a.is_deleted() for a in before) and not pool.lost()
    for entry, old in zip(pool.layers, want):
        for a, w in zip(entry, old):
            got = np.asarray(a)
            np.testing.assert_array_equal(got[copy], w[block])
            w[copy] = w[block]
            np.testing.assert_array_equal(got, w)


def test_speculation_donates_each_model_s_slice_of_the_one_pool():
    """The draft's and the target's steps each consume their own slice
    of ``pool.layers`` and the engine reassembles the list: greedy
    outputs equal ``generate()``'s and nothing is lost on the way."""
    from paddle_tpu.serving import SpeculativeConfig

    target = _llama()
    paddle.seed(1)
    draft = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=1))
    draft.eval()
    eng = _engine(target, speculative=SpeculativeConfig(
        draft_model=draft, num_draft_tokens=K_DRAFT))
    prompt = np.arange(1, 12, dtype=np.int32)
    got = eng.generate([prompt], max_new_tokens=6)[0]
    ref = target.generate(paddle.to_tensor(prompt[None, :]),
                          max_new_tokens=6, temperature=0.0)
    np.testing.assert_array_equal(got, np.asarray(ref.numpy())[0])
    assert not eng.pool.lost()
    assert len(eng.pool.layers) == target.config.num_hidden_layers + 1
    eng.pool.check_leaks()


@pytest.mark.parametrize("T, bs, k", [(4, 16, 8), (256, 16, 8), (12, 4, 3),
                                      (5, 8, 2)])
def test_a_sidecar_row_write_is_the_position_by_position_write(T, bs, k):
    """``scatter_block_rows`` (a sidecar kept a row a block, whole rows
    read, changed and written back) against writing each position where
    the table says, one at a time."""
    from paddle_tpu.models.sdar_moe import scatter_block_rows

    rng = np.random.default_rng(T * 100 + bs)
    B, nb, width = 3, 128, 24
    pool = rng.integers(0, 99, size=(nb, bs * k)).astype(np.int32)
    table = rng.permutation(np.arange(1, nb))[:B * width].reshape(B, width) \
        .astype(np.int32)
    start = rng.integers(0, (width - 1) * bs - T, size=B).astype(np.int32)
    start[0] = start[0] // bs * bs          # one aligned, the others not
    new = rng.integers(100, 999, size=(B, T, k)).astype(np.int32)
    wmask = rng.random((B, T)) < 0.7
    wmask[1] = False                        # a sequence that writes nothing
    want = pool.reshape(nb, bs, k).copy()
    for b in range(B):
        for t in range(T):
            if wmask[b, t]:
                p = start[b] + t
                want[table[b, p // bs], p % bs] = new[b, t]
    got = np.asarray(jax.jit(scatter_block_rows)(
        pool, new, table, start, wmask)).reshape(nb, bs, k)
    # block 0 is the garbage sink: whatever lands there is never read
    np.testing.assert_array_equal(got[1:], want[1:])
