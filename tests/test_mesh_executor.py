"""distributed.executor — runtime SPMD mesh execution (ISSUE 8 done bar).

Runs on the conftest-forced 8-virtual-device CPU backend: 20 train-step
losses on a (2,2,2) mesh allclose to the (1,1,1) run with exactly one
compile per step signature, serving tokens with tp=2 exact vs
``generate()`` with zero retraces, S209 reconciliation clean for all
three registered steps, and kill/resume bit-identical through the
shard-aware checkpoint path.
"""
import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.distributed import executor as ex_mod
from paddle_tpu.distributed.executor import MeshExecutor, as_executor
from paddle_tpu.distributed.sharding import (get_sharding_spec,
                                             mark_sharding, shard_tensor)
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import Engine, ServingConfig

AXES = {"data": 2, "fsdp": 2, "tp": 2}
BATCH, SEQ = 4, 16


@pytest.fixture(autouse=True)
def _fresh_registry():
    yield
    ex = ex_mod.current_executor()
    if ex is not None:
        ex.close()


class _LMLoss:
    """loss_fn(outputs, labels) for the hapi train step."""

    def __call__(self, logits, labels):
        vocab = logits.shape[-1]
        return nn.functional.cross_entropy(
            logits.reshape([-1, vocab]), labels.reshape([-1]))


def _llama_hapi(mesh):
    paddle.seed(0)
    cfg = LlamaConfig.tiny(max_position_embeddings=SEQ)
    net = LlamaForCausalLM(cfg)
    model = paddle.Model(net)
    opt = paddle.optimizer.AdamW(3e-4, parameters=net.parameters())
    model.prepare(opt, _LMLoss(), mesh=mesh)
    return model, cfg


def _batches(n, cfg, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size,
                        (BATCH, SEQ)).astype(np.int32) for _ in range(n)]


def _train(model, batches):
    losses = []
    for toks in batches:
        losses.append(model.train_batch([toks], [toks.astype(np.int64)]))
    return np.asarray(losses, np.float64)


# ---------------------------------------------------------------------------
# mesh construction
# ---------------------------------------------------------------------------

class TestMeshBuild:
    def test_axes_and_devices(self):
        ex = MeshExecutor(AXES)
        assert dict(ex.mesh.shape) == AXES
        assert ex.mesh.size == 8
        ex.close()

    def test_raises_when_devices_scarce(self):
        with pytest.raises(ValueError, match="needs 16 devices but only 8"):
            MeshExecutor({"data": 16, "fsdp": 1, "tp": 1})
        assert ex_mod.current_executor() is None

    def test_as_executor_coercions(self):
        ex = MeshExecutor(AXES)
        assert as_executor(ex) is ex
        ex2 = as_executor(ex.mesh)
        assert dict(ex2.mesh.shape) == AXES
        ex.close()
        ex2.close()

    def test_registry_and_default_shardplan_mesh(self):
        assert ex_mod.default_shardplan_mesh() is None
        ex = MeshExecutor(AXES)
        assert ex_mod.current_executor() is ex
        assert ex_mod.default_shardplan_mesh() == AXES
        assert ex_mod.active_mesh() is ex.mesh
        ex.close()
        assert ex_mod.default_shardplan_mesh() is None

    def test_clean_spec_drops_unknown_and_indivisible(self):
        ex = MeshExecutor(AXES)
        assert ex.clean_spec(PartitionSpec("sp"), (8,)) == PartitionSpec()
        assert ex.clean_spec(PartitionSpec("data"), (7,)) == PartitionSpec()
        assert ex.clean_spec(
            PartitionSpec("fsdp", "tp"), (8, 8)) == \
            PartitionSpec("fsdp", "tp")
        assert ex.shard_shape((8, 8), PartitionSpec("fsdp", "tp")) == (4, 4)
        ex.close()


# ---------------------------------------------------------------------------
# sharding-helper executor context (satellite: mark_sharding/shard_tensor)
# ---------------------------------------------------------------------------

class TestShardingHelpersExecutorContext:
    def test_shard_tensor_uses_executor_mesh(self):
        ex = MeshExecutor(AXES)
        t = paddle.to_tensor(np.ones((8, 8), np.float32))
        out = shard_tensor(t, placements=["fsdp", "tp"])
        assert out._value.sharding.shard_shape((8, 8)) == (4, 4)
        ex.close()

    def test_shard_tensor_unknown_axis_still_noop(self):
        ex = MeshExecutor(AXES)
        t = paddle.to_tensor(np.ones((8, 8), np.float32))
        assert shard_tensor(t, placements=["sp", None]) is t
        ex.close()

    def test_mark_sharding_uses_executor_mesh(self):
        ex = MeshExecutor(AXES)
        p = paddle.to_tensor(np.ones((8, 4), np.float32))
        mark_sharding(p, ["fsdp", None])
        assert get_sharding_spec(p) == PartitionSpec("fsdp", None)
        assert p._value.sharding.shard_shape((8, 4)) == (4, 4)
        ex.close()

    def test_no_mesh_anywhere_is_still_noop(self):
        t = paddle.to_tensor(np.ones((8, 8), np.float32))
        assert shard_tensor(t, placements=["fsdp", "tp"]) is t


# ---------------------------------------------------------------------------
# train: loss parity + compile accounting + S209 reconciliation
# ---------------------------------------------------------------------------

class TestMeshTrain:
    def test_train_parity_and_reconcile(self):
        cfg = LlamaConfig.tiny(max_position_embeddings=SEQ)
        batches = _batches(20, cfg)

        single, _ = _llama_hapi(mesh={"data": 1, "fsdp": 1, "tp": 1})
        ref = _train(single, batches)
        assert single._train_step_fn.compiles == 2  # pre/post-slot warmup
        single._mesh_executor.close()

        sharded, _ = _llama_hapi(mesh=dict(AXES))
        ex = sharded._mesh_executor
        assert ex is not None and ex.mesh.size == 8
        got = _train(sharded, batches)

        # exactly one compile per step signature on BOTH meshes: the
        # warmup pair (entry without slots, entry with slots), stable
        # across all 20 steps
        assert sharded._train_step_fn.compiles == 2
        assert np.all(np.isfinite(got))
        assert np.allclose(got, ref, rtol=5e-3, atol=5e-3), (
            f"sharded losses diverged:\n{got}\nvs\n{ref}")

        # params actually live sharded on the mesh
        q = dict(sharded.network.named_parameters())
        name = next(n for n in q if n.endswith("q_proj.weight"))
        val = q[name]._value
        assert len(val.sharding.device_set) == 8
        assert val.sharding.shard_shape(val.shape) != tuple(val.shape)

        # S209 reconciliation: compiled program vs static plan — clean
        toks = batches[0]
        plan, diags = ex.reconcile_train(
            sharded, [toks], [toks.astype(np.int64)])
        assert plan.per_chip_peak_hbm_bytes > 0
        assert diags == [], [str(d) for d in diags]
        assert "hapi::train_step" in ex.reports
        ex.close()


# ---------------------------------------------------------------------------
# serving: token parity + no retraces + S209 reconciliation
# ---------------------------------------------------------------------------

class TestMeshServing:
    def _engine(self):
        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny())
        model.eval()
        cfg = ServingConfig(max_batch_size=4, block_size=4, num_blocks=64,
                            max_queue_len=16, mesh=dict(AXES))
        return Engine(model, cfg), model

    def test_token_parity_and_reconcile(self):
        eng, model = self._engine()
        ex = eng.mesh_executor
        assert ex is not None and ex.mesh.size == 8

        # KV pool actually sharded on tp
        k0, _v0 = eng.pool.layers[0]
        assert len(k0.sharding.device_set) == 8
        assert k0.sharding.shard_shape(k0.shape)[2] == k0.shape[2] // 2

        rng = np.random.RandomState(0)
        prompts = [rng.randint(1, 256, size=(L,)).astype(np.int32)
                   for L in (3, 7, 5)]
        outs = eng.generate(prompts, max_new_tokens=8)
        # token-exact vs sequential generate() ON THE SAME SHARDED MODEL
        for prompt, out in zip(prompts, outs):
            ref = model.generate(paddle.to_tensor(prompt[None, :]),
                                 temperature=0.0, use_static_cache=True,
                                 max_new_tokens=8)
            np.testing.assert_array_equal(np.asarray(out),
                                          np.asarray(ref.numpy())[0])

        # the no-retrace contract holds under SPMD
        assert eng._decode_step.retraces == 0
        assert eng._prefill_step.retraces == 0
        assert eng.decode_cache_size() == 1
        assert eng.prefill_cache_size() == 1

        # S209 reconciliation for BOTH serving steps — clean; and the
        # AOT audit itself must not count as a retrace
        results = eng.reconcile_mesh()
        assert set(results) == {"serving::decode_step",
                                "serving::prefill_step"}
        for name, (plan, diags) in results.items():
            assert plan.per_chip_peak_hbm_bytes > 0, name
            assert diags == [], (name, [str(d) for d in diags])
        assert eng._decode_step.retraces == 0
        assert eng._prefill_step.retraces == 0
        ex.close()

    def test_reconcile_requires_mesh(self):
        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny())
        model.eval()
        eng = Engine(model, ServingConfig(max_batch_size=2, block_size=4,
                                          num_blocks=16))
        with pytest.raises(RuntimeError, match="mesh"):
            eng.reconcile_mesh()

    def test_sequential_generate_static_kv_sharded(self):
        """Satellite: sequential ``generate()``'s static KV caches are
        committed sharded on the tp axis under an active mesh — same
        layout as the paged pool — with token-exact outputs."""
        from paddle_tpu.models.generation import _static_caches

        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny())
        model.eval()
        prompt = np.random.RandomState(3).randint(
            1, 256, size=(7,)).astype(np.int32)
        ref = model.generate(paddle.to_tensor(prompt[None, :]),
                             temperature=0.0, use_static_cache=True,
                             max_new_tokens=8)
        ref = np.asarray(ref.numpy())

        ex = MeshExecutor(AXES)
        assert ex.static_kv_spec() == PartitionSpec(
            None, None, ex.layout.tp_axis, None)
        caches = _static_caches(model, batch=1, max_len=32)
        kv_heads = caches[0].k.shape[2]
        for c in caches:
            for buf in (c.k, c.v):
                assert len(buf.sharding.device_set) == 8
                assert buf.sharding.shard_shape(buf.shape)[2] \
                    == kv_heads // 2
        out = model.generate(paddle.to_tensor(prompt[None, :]),
                             temperature=0.0, use_static_cache=True,
                             max_new_tokens=8)
        np.testing.assert_array_equal(np.asarray(out.numpy()), ref)
        ex.close()


# ---------------------------------------------------------------------------
# shard-aware checkpoint: host-gather save, re-shard restore
# ---------------------------------------------------------------------------

class TestMeshCheckpoint:
    def test_kill_resume_bit_identical(self):
        from paddle_tpu.resilience.checkpoint import (apply_state,
                                                      collect_state)

        cfg = LlamaConfig.tiny(max_position_embeddings=SEQ)
        batches = _batches(8, cfg, seed=1)
        model, _ = _llama_hapi(mesh=dict(AXES))
        _train(model, batches[:5])

        snap = collect_state(model.network, model._optimizer)
        # host-gather: no device (jax) arrays survive in the snapshot —
        # every array leaf is gathered host numpy
        flat = jax.tree_util.tree_leaves(snap)
        assert not any(isinstance(v, jax.Array) for v in flat)
        assert any(isinstance(v, np.ndarray) for v in flat)

        cont = _train(model, batches[5:])

        apply_state(snap, model.network, model._optimizer)
        # restore re-shards onto the mesh (not a single-device rebind)
        q = dict(model.network.named_parameters())
        name = next(n for n in q if n.endswith("q_proj.weight"))
        assert len(q[name]._value.sharding.device_set) == 8
        resumed = _train(model, batches[5:])

        np.testing.assert_array_equal(cont, resumed)
        model._mesh_executor.close()


# ---------------------------------------------------------------------------
# observability gauges
# ---------------------------------------------------------------------------

class TestMeshGauges:
    def test_mesh_gauges_exported(self):
        import paddle_tpu.observability as obs

        obs.enable()
        try:
            reg = obs.get_registry()
            ex = MeshExecutor(AXES)
            assert reg.gauge("mesh_num_devices").value() == 8.0
            for ax, sz in AXES.items():
                assert reg.gauge("mesh_axis_sizes").value(axis=ax) == sz
            ex.close()
        finally:
            obs.disable()
