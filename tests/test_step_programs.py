"""How a step program is made, kept and told which kernels to trace:
the one table of compiled steps on the model
(``models/generation.py::cached_step``) and the one rule for the path
and the lowering (``kernels/fusion.py``)."""
import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.mesh import abstract_mesh, use_mesh
from paddle_tpu.kernels import fusion
from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM, SDARMoEConfig,
                               SDARMoEForCausalLM)
from paddle_tpu.models import generation as gen
from paddle_tpu.serving import Engine, ServingConfig
from paddle_tpu.serving.sampling import make_sampled_decode_step
from paddle_tpu.serving.speculative import (make_draft_propose_step,
                                            make_spec_verify_step)


def _llama(seed=0, **overrides):
    paddle.seed(seed)
    model = LlamaForCausalLM(LlamaConfig.tiny(**overrides))
    model.eval()
    return model


def _sdar(seed=0):
    paddle.seed(seed)
    model = SDARMoEForCausalLM(SDARMoEConfig.tiny())
    model.eval()
    return model


# factory -> (model maker, the arguments that name one step, arguments
# that must each name another)
FACTORIES = {
    "decode": (gen.make_decode_step, _llama, {}, []),
    "beam_decode": (gen.make_beam_decode_step, _llama, {}, []),
    "prefill": (gen.make_prefill_step, _llama, {}, []),
    "paged_decode": (gen.make_paged_decode_step, _llama,
                     dict(fused=True),
                     [dict(fused=False),
                      dict(fused=True, kv_cache_dtype="int8"),
                      dict(fused=True, kv_cache_dtype="fp8")]),
    "chunked_prefill": (gen.make_chunked_prefill_step, _llama,
                        dict(fused=True),
                        [dict(fused=False),
                         dict(fused=True, kv_cache_dtype="int8")]),
    "block_chunked_prefill": (gen.make_chunked_prefill_step, _sdar,
                              dict(fused=True), [dict(fused=False)]),
    "paged_block": (gen.make_paged_block_step, _sdar, dict(fused=True),
                    [dict(fused=False)]),
    "moe_block": (gen.make_moe_block_step,
                  lambda: _llama(moe_num_experts=4, moe_top_k=2), {}, []),
    "ring_sp": (gen.make_ring_sp_step,
                lambda: _llama(context_parallel="ring"), {},
                [dict(mesh=abstract_mesh({"data": 2, "sp": 2}))]),
    "sampled_decode": (make_sampled_decode_step, _llama, dict(fused=True),
                       [dict(fused=False),
                        dict(fused=True, kv_cache_dtype="int8")]),
    "draft_propose": (make_draft_propose_step, _llama,
                      dict(num_draft=3, fused=True),
                      [dict(num_draft=2, fused=True),
                       dict(num_draft=3, fused=False)]),
    "spec_verify": (make_spec_verify_step, _llama,
                    dict(num_draft=3, fused=True),
                    [dict(num_draft=2, fused=True),
                     dict(num_draft=3, fused=False)]),
}


@pytest.mark.parametrize("kind", sorted(FACTORIES))
def test_one_key_one_step(kind):
    """The same arguments give the same object; other ``fused``,
    ``kv_cache_dtype``, ``num_draft`` or mesh another; and the table is
    the only thing a factory leaves on the model."""
    make, model_of, same, others = FACTORIES[kind]
    model = model_of()
    before = set(vars(model))
    step = make(model, **same)
    assert make(model, **same) is step
    if "fused" in same:
        # None is the fused math, on every backend
        assert make(model, **{**same, "fused": None}) is step
    steps = [step] + [make(model, **kw) for kw in others]
    assert len({id(s) for s in steps}) == len(steps)
    assert make(model, **same) is step
    assert set(vars(model)) - before == {"_compiled_steps"}
    assert {k[0] for k in model._compiled_steps} == {kind}
    assert kind in {k for _fn, k in gen.registered_decode_step_entries()}


def _paged_inputs(eng):
    """One sequence of 5 tokens on blocks 1.. of the engine's idle pool."""
    S = eng.config.max_batch_size
    table = np.zeros((S, eng.max_blocks_per_seq), np.int32)
    table[0, :2] = (1, 2)
    return S, table


def _run_decode(model):
    step = gen.make_decode_step(model)
    caches = [(c.k, c.v) for c in gen._static_caches(model, 2, 12)]
    return step, step(np.ones((2, 1), np.int32), caches, np.int32(4))[0]


def _run_paged_decode(model):
    eng = Engine(model, ServingConfig(max_batch_size=2, block_size=8,
                                      num_blocks=16))
    step = gen.make_paged_decode_step(model)
    S, table = _paged_inputs(eng)
    lengths = np.zeros((S,), np.int32)
    lengths[0] = 5
    return step, step(np.ones((S, 1), np.int32), eng.pool.layers, table,
                      lengths)[0]


def _run_chunked_prefill(model):
    eng = Engine(model, ServingConfig(max_batch_size=2, block_size=8,
                                      num_blocks=16))
    step = gen.make_chunked_prefill_step(model)
    _, table = _paged_inputs(eng)
    ids = np.zeros((1, eng.chunk_tokens), np.int32)
    ids[0, :5] = (3, 1, 4, 1, 5)
    return step, step(ids, eng.pool.layers, table[:1],
                      np.zeros((1,), np.int32), np.int32(4))[0]


def _run_moe_block(model):
    step = gen.make_moe_block_step(model)
    return step, step(np.arange(16, dtype=np.int32).reshape(2, 8))


def _run_ring_sp(model):
    step = gen.make_ring_sp_step(model)
    return step, step(np.arange(16, dtype=np.int32).reshape(2, 8))


REBIND = {
    "decode": (_llama, _run_decode),
    "paged_decode": (_llama, _run_paged_decode),
    "chunked_prefill": (_llama, _run_chunked_prefill),
    "moe_block": (lambda seed=0: _llama(seed, moe_num_experts=4,
                                        moe_top_k=2), _run_moe_block),
    "ring_sp": (lambda seed=0: _llama(seed, context_parallel="ring"),
                _run_ring_sp),
}


@pytest.mark.parametrize("kind", sorted(REBIND))
def test_a_rebound_weight_is_served_without_a_recompile(kind):
    """No step closes over the weights: after ``set_state_dict`` the same
    step, with the one executable it has, gives what a model that was
    born with those weights gives."""
    model_of, run = REBIND[kind]
    model, other = model_of(0), model_of(1)
    step, first = run(model)
    assert step._cache_size() == 1
    model.set_state_dict({k: v.numpy()
                          for k, v in other.state_dict().items()})
    again, second = run(model)
    assert again is step and step._cache_size() == 1
    _, want = run(other)
    np.testing.assert_array_equal(np.asarray(second), np.asarray(want))
    assert not np.array_equal(np.asarray(first), np.asarray(second))


def test_a_new_buffer_gets_a_fresh_step():
    """``jit_with_weights`` takes its tensor list when it is built: a
    model that has since grown a buffer is given a new step when one is
    asked for, and the old one is left to whoever holds it."""
    from paddle_tpu.core.tensor import Tensor

    model = _llama()
    step = gen.make_paged_decode_step(model)
    model.register_buffer("late", Tensor(np.zeros((2,), np.float32)))
    assert not step.holds(model)
    fresh = gen.make_paged_decode_step(model)
    assert fresh is not step and fresh.holds(model)
    assert gen.make_paged_decode_step(model) is fresh


# ---------------------------------------------- which program is traced
@pytest.fixture
def fused_calls(monkeypatch):
    """Counts the traces that reach the fused decode and chunk math."""
    from paddle_tpu.kernels import chunked_prefill, paged_attention

    calls = {"decode": 0, "chunk": 0}

    def counting(name, fn):
        def wrapper(*args, **kwargs):
            calls[name] += 1
            return fn(*args, **kwargs)
        return wrapper

    monkeypatch.setattr(paged_attention, "fused_paged_decode", counting(
        "decode", paged_attention.fused_paged_decode))
    monkeypatch.setattr(chunked_prefill, "fused_chunked_attention", counting(
        "chunk", chunked_prefill.fused_chunked_attention))
    return calls


def _serve(cfg):
    model = _llama()
    eng = Engine(model, cfg)
    prompt = np.arange(1, 8, dtype=np.int32)
    out = eng.generate([prompt], max_new_tokens=4)[0]
    ref = model.generate(paddle.to_tensor(prompt[None, :]),
                         max_new_tokens=4, temperature=0.0)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ref.numpy())[0])
    return eng


def test_the_default_engine_traces_the_fused_math_on_the_cpu(fused_calls):
    """``ServingConfig()`` names no mode and the backend is the CPU: the
    engine traces the fused decode and chunk math (their XLA lowering),
    which is what the chip's cells run."""
    assert jax.default_backend() == "cpu"
    layers = LlamaConfig.tiny().num_hidden_layers
    eng = _serve(ServingConfig(max_batch_size=2, block_size=8,
                               num_blocks=16))
    assert eng.config.fused_kernels is None
    assert fused_calls == {"decode": layers, "chunk": layers}


def test_the_pinned_gather_path_reaches_no_fused_math(fused_calls):
    _serve(ServingConfig(max_batch_size=2, block_size=8, num_blocks=16,
                         fused_kernels=False))
    assert fused_calls == {"decode": 0, "chunk": 0}


def test_under_a_live_mesh_the_gather_path_is_traced(fused_calls):
    """The kernels have no partitioning rule: whatever was pinned, a
    trace under a mesh takes the gather path."""
    from paddle_tpu.distributed import executor as ex_mod

    try:
        _serve(ServingConfig(max_batch_size=2, block_size=8,
                             num_blocks=16, fused_kernels=True,
                             mesh={"data": 1, "tp": 2}))
        assert fused_calls == {"decode": 0, "chunk": 0}
    finally:
        ex = ex_mod.current_executor()
        if ex is not None:
            ex.close()


def test_fusion_enabled_is_the_whole_rule():
    assert fusion.fusion_enabled()
    assert fusion.resolve_serving_fusion(None) is True
    with fusion.serving_fusion(False):
        assert not fusion.fusion_enabled()
        with fusion.serving_fusion(True):
            assert fusion.fusion_enabled()
    with use_mesh(abstract_mesh({"tp": 2})):
        assert not fusion.fusion_enabled()
        with fusion.serving_fusion(True):
            assert not fusion.fusion_enabled()
    assert fusion.fusion_enabled()


@pytest.mark.parametrize("asked,forced,want", [
    ((None, None), False, (False, True)),     # off the chip: XLA lowering
    ((True, None), False, (True, True)),      # a kernel test's interpreter
    ((True, False), False, (True, False)),    # compiled for a described chip
    ((None, None), True, (True, True)),       # the analysers' pallas_call
    ((None, False), True, (True, True)),
    ((False, None), True, (False, True)),     # an explicit request wins
])
def test_pallas_lowering(asked, forced, want):
    assert jax.default_backend() == "cpu"
    with fusion.force_pallas_interpret(forced):
        assert fusion.pallas_lowering(*asked) == want


# ------------------------------- the engine's steps are the factories'
def test_the_engine_runs_the_objects_the_factories_return():
    """The benchmark's check calls the factories with the engine's own
    settings and counts on getting the programs the engine compiled."""
    model = _llama()
    for kv in (None, "int8"):
        eng = Engine(model, ServingConfig(max_batch_size=2, block_size=8,
                                          num_blocks=16,
                                          kv_cache_dtype=kv))
        kw = dict(fused=eng.config.fused_kernels,
                  kv_cache_dtype=eng.config.kv_cache_dtype)
        assert eng._decode_step._fn is gen.make_paged_decode_step(model,
                                                                  **kw)
        assert eng._prefill_step._fn is gen.make_chunked_prefill_step(
            model, **kw)
        assert eng._sampled_decode_step._fn is make_sampled_decode_step(
            model, **kw)
    assert len(model._compiled_steps) == 6


def test_the_block_engine_runs_the_objects_the_factories_return():
    model = _sdar()
    eng = Engine(model, ServingConfig(max_batch_size=2, block_size=8,
                                      num_blocks=32, chunk_tokens=16))
    fused = eng.config.fused_kernels
    assert eng._decode_step._fn is gen.make_paged_block_step(model,
                                                             fused=fused)
    assert eng._prefill_step._fn is gen.make_chunked_prefill_step(
        model, fused=fused, kv_cache_dtype=eng.config.kv_cache_dtype)
    assert eng._decode_step.__name__ == "paged_block_step"
    assert eng._prefill_step.__name__ == "chunked_prefill_step"


# ------------------------------ the token is chosen where its logits are
def _afmoe(seed=0):
    from paddle_tpu.models import AfmoeConfig, AfmoeForCausalLM

    paddle.seed(seed)
    model = AfmoeForCausalLM(AfmoeConfig.tiny())
    model.eval()
    return model


# a model a case: (its maker, the engine's options)
CHOOSERS = {
    "llama": (_llama, {}),
    "llama_int8": (_llama, dict(kv_cache_dtype="int8")),
    "afmoe": (_afmoe, dict(max_model_len=64)),   # its pair of tables
}
PROGRAMS = {"decode": "paged_decode_step", "chunk": "chunked_prefill_step"}


class _Chooser:
    """One sequence of five prompt tokens on blocks 1 and 2 of an idle
    pool, driven by hand: its chunk, then one decode step."""

    def __init__(self, case):
        model_of, engine_kw = CHOOSERS[case]
        self.model = model_of()
        self.eng = eng = Engine(self.model, ServingConfig(
            max_batch_size=2, block_size=8, num_blocks=16, chunk_tokens=16,
            **engine_kw))
        kw = dict(fused=eng.config.fused_kernels,
                  kv_cache_dtype=eng.config.kv_cache_dtype)
        self.steps = {"chunk": gen.make_chunked_prefill_step(self.model, **kw),
                      "decode": gen.make_paged_decode_step(self.model, **kw)}
        self.grouped = eng.window is not None

    def _tables(self, rows):
        _, table = _paged_inputs(self.eng)
        table = table[:rows]
        return (table, table.copy()) if self.grouped else table

    def args(self, program):
        """The program's arguments over the pool as it stands (the
        chunk's, or a decode step's behind that chunk)."""
        S = self.eng.config.max_batch_size
        if program == "chunk":
            ids = np.zeros((1, self.eng.chunk_tokens), np.int32)
            ids[0, :5] = (3, 1, 4, 1, 5)
            return (ids, self.eng.pool.layers, self._tables(1),
                    np.zeros((1,), np.int32), np.int32(4))
        lengths = np.zeros((S,), np.int32)
        lengths[0] = 5
        return (np.full((S, 1), 9, np.int32), self.eng.pool.layers,
                self._tables(S), lengths)

    def bind(self, pools):
        self.eng.pool.layers = [tuple(entry) for entry in pools]

    def both(self, program):
        """``(logits, ids)`` of ONE call of the program (neither reader
        gives both), the pool bound again."""
        step = self.steps[program]
        first, pools = step._jitted(step._weights(), *self.args(program))
        self.bind(pools)
        return np.asarray(first[0]), np.asarray(first[1])

    def upto(self, program):
        """The pool as ``program`` finds it in service."""
        if program == "decode":
            self.bind(self.steps["chunk"](*self.args("chunk"))[1])
        return self


CHOOSER_CASES = [(c, p) for c in sorted(CHOOSERS) for p in sorted(PROGRAMS)]


@pytest.mark.parametrize("case,program", CHOOSER_CASES)
def test_a_step_chooses_the_argmax_of_the_logits_it_returns(case, program):
    """The ids are ``np.argmax`` of the logits of the same call, int32,
    one a row; the two readers hand out one call's results, logits or
    ids, in the same place; and both run ONE executable."""
    hand = _Chooser(case).upto(program)
    step = hand.steps[program]
    logits, ids = hand.both(program)
    rows = 1 if program == "chunk" else hand.eng.config.max_batch_size
    assert logits.shape == (rows, hand.model.config.vocab_size)
    assert logits.dtype == np.float32
    assert ids.shape == (rows,) and ids.dtype == np.int32
    np.testing.assert_array_equal(ids, np.argmax(logits, axis=-1))
    # the same inputs write the same K/V again: the readers agree
    read, pools = step(*hand.args(program))
    hand.bind(pools)
    chosen, pools = step.ids(*hand.args(program))
    hand.bind(pools)
    if hand.grouped:
        (read, stats), (chosen, stats_again) = read, chosen
        np.testing.assert_array_equal(np.asarray(stats),
                                      np.asarray(stats_again))
        assert np.asarray(stats).shape == (3,)
    np.testing.assert_array_equal(np.asarray(read), logits)
    np.testing.assert_array_equal(np.asarray(chosen), ids)
    assert step._cache_size() == 1


@pytest.mark.parametrize("case,program", CHOOSER_CASES)
def test_of_two_equal_maxima_the_lower_index_wins(case, program):
    """A head of zeros but for two identical columns: two logits of a
    row are equal and, given the sign that makes them so, the largest;
    the program takes the first, as ``np.argmax`` does."""
    hand = _Chooser(case).upto(program)
    head = hand.model.lm_head.weight
    low, high = 11, 200
    column = np.random.default_rng(0).standard_normal(head.shape[0])
    for sign in (1.0, -1.0):
        planted = np.zeros(head.shape, np.float32)
        planted[:, low] = planted[:, high] = sign * column
        head._value = jax.numpy.asarray(planted).astype(head._value.dtype)
        logits, ids = hand.both(program)
        if logits[0, low] > 0:
            break
    assert logits[0, low] == logits[0, high] == logits[0].max() > 0
    assert ids[0] == low == np.argmax(logits[0])
    assert hand.steps[program]._cache_size() == 1


@pytest.mark.parametrize("case,program", CHOOSER_CASES)
def test_a_choosing_step_is_one_program_under_its_name(case, program):
    """The lowered text: one module, named after the step (the trace's
    ``jit_paged_decode_step`` / ``jit_chunked_prefill_step``), every
    pool leaf still an input that an output aliases."""
    hand = _Chooser(case).upto(program)
    args = hand.args(program)
    leaves = sum(len(entry) for entry in hand.eng.pool.layers)
    text = hand.steps[program].lower(*args).as_text()
    assert text.count("module @") == 1
    assert f"module @jit_{PROGRAMS[program]} " in text
    assert text.count("tf.aliasing_output") == leaves


def test_a_step_that_chooses_nothing_has_no_ids():
    step = gen.make_paged_block_step(_sdar())
    with pytest.raises(TypeError, match="chooses no token"):
        step.ids()
