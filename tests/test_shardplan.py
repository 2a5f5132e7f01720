"""Static SPMD shard-plan analyzer (paddle_tpu.analysis.shardplan).

Golden-value contracts first (hand-computed ring-collective bytes and
shard-aware peak HBM for a matmul + all-reduce), then the propagation
rules, the S204–S208 diagnostics, the canonical llama SpecLayout
readiness, the end-to-end audit, the `lint_tpu.py --shardplan` CLI
exit-code contract, and the Model.fit / ServingConfig opt-in wiring.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.analysis import (PlanRequest, audit_shardplan,
                                 check_sharding_readiness, plan_jaxpr)
from paddle_tpu.analysis.xray import CHIPS, ChipProfile
from paddle_tpu.distributed.sharding import (SpecLayout, llama_param_role,
                                             llama_param_specs)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _codes(diags):
    return [d.code for d in diags]


# ---------------------------------------------------------------------------
# golden values: hand-computed collective bytes and per-chip peak HBM
# ---------------------------------------------------------------------------

class TestGoldenMatmul:
    """x[8,64] P(None,'tp') @ w[64,32] P('tp',None) on mesh {tp:4}.

    Both contraction sides are sharded on 'tp', so GSPMD runs the local
    partial matmul and ONE planned all-reduce of the f32 [8,32] output:

    - payload          = 8*32*4           = 1024 B
    - ring all-reduce  = 2*S*(n-1)/n      = 2*1024*3/4 = 1536 B/chip
    - per-chip peak at the dot: x 2048/4 + w 8192/4 + out 1024 = 3584 B
    """

    @pytest.fixture(scope="class")
    def report(self):
        f = lambda x, w: x @ w  # noqa: E731
        closed = jax.make_jaxpr(f)(jnp.zeros((8, 64), jnp.float32),
                                   jnp.zeros((64, 32), jnp.float32))
        return plan_jaxpr(closed, [PS(None, "tp"), PS("tp", None)],
                          mesh={"tp": 4}, name="golden")

    def test_single_planned_all_reduce(self, report):
        assert len(report.collectives) == 1
        c = report.collectives[0]
        assert c.kind == "all_reduce"
        assert c.axes == ("tp",)
        assert c.planned
        assert c.primitive == "dot_general"

    def test_collective_bytes_golden(self, report):
        c = report.collectives[0]
        assert c.payload_bytes == 1024
        assert c.bytes_moved == 1536
        assert report.comm_bytes == 1536

    def test_collective_time_uses_ici_profile(self, report):
        c = report.collectives[0]
        cpu = CHIPS["cpu"]
        assert c.time_s == pytest.approx(
            1536 / cpu.ici_bandwidth + cpu.ici_latency)

    def test_per_chip_peak_hbm_golden(self, report):
        assert report.per_chip_peak_hbm_bytes == 3584

    def test_clean_plan_has_no_diagnostics(self, report):
        assert report.diagnostics == []


class TestGoldenShardedParamPeak:
    """A [64,64] f32 param sharded 2-way on 'fsdp' through w*2: both the
    operand and the result live at 8192 B/chip, so the peak is exactly
    half the replicated plan's 32768."""

    def test_two_way_sharding_halves_peak(self):
        closed = jax.make_jaxpr(lambda w: w * 2.0)(
            jnp.zeros((64, 64), jnp.float32))
        sharded = plan_jaxpr(closed, [PS("fsdp", None)], mesh={"fsdp": 2})
        repl = plan_jaxpr(closed, [PS()], mesh={"fsdp": 2})
        assert repl.per_chip_peak_hbm_bytes == 32768
        assert sharded.per_chip_peak_hbm_bytes == 16384
        assert sharded.collectives == []  # elementwise needs no comm


# ---------------------------------------------------------------------------
# propagation rules
# ---------------------------------------------------------------------------

class TestPropagationRules:
    def test_transpose_carries_sharding_into_contraction(self):
        # x.T moves the 'tp' shard from dim 0 to the contraction dim, so
        # the dot still resolves to one planned all-reduce — no gather.
        closed = jax.make_jaxpr(lambda x, w: x.T @ w)(
            jnp.zeros((64, 8), jnp.float32), jnp.zeros((64, 32), jnp.float32))
        r = plan_jaxpr(closed, [PS("tp", None), PS("tp", None)],
                       mesh={"tp": 4})
        assert [(c.kind, c.planned) for c in r.collectives] == [
            ("all_reduce", True)]

    def test_reshape_keeps_major_dim_sharding(self):
        # (8,64)->(512,): dim 0 is the MAJOR dim of the merge group, so
        # its sharding survives and the following sum is a planned psum.
        closed = jax.make_jaxpr(lambda x: x.reshape(512).sum())(
            jnp.zeros((8, 64), jnp.float32))
        r = plan_jaxpr(closed, [PS("tp", None)], mesh={"tp": 4})
        assert [(c.kind, c.planned) for c in r.collectives] == [
            ("all_reduce", True)]

    def test_reshape_drops_minor_dim_sharding_with_gather(self):
        # sharding the MINOR dim of a merge cannot survive a reshape:
        # the shards interleave, so the planner charges an unplanned
        # gather at the reshape itself.
        closed = jax.make_jaxpr(lambda x: x.reshape(512).sum())(
            jnp.zeros((8, 64), jnp.float32))
        r = plan_jaxpr(closed, [PS(None, "tp")], mesh={"tp": 4})
        assert ("all_gather", False, "reshape") in [
            (c.kind, c.planned, c.primitive) for c in r.collectives]

    def test_elementwise_spec_conflict_is_unplanned(self):
        closed = jax.make_jaxpr(lambda x, y: x + y)(
            jnp.zeros((16, 16), jnp.float32), jnp.zeros((16, 16), jnp.float32))
        r = plan_jaxpr(closed, [PS("tp", None), PS(None, "tp")],
                       mesh={"tp": 4}, s205_bytes=1)
        assert [(c.kind, c.planned) for c in r.collectives] == [
            ("all_gather", False)]
        assert "S205" in _codes(r.diagnostics)

    def test_reduce_over_sharded_dim_is_planned_psum(self):
        closed = jax.make_jaxpr(lambda x: x.sum(axis=0))(
            jnp.zeros((8, 64), jnp.float32))
        r = plan_jaxpr(closed, [PS("tp", None)], mesh={"tp": 4})
        assert [(c.kind, c.planned) for c in r.collectives] == [
            ("all_reduce", True)]

    def test_reduce_over_unsharded_dim_is_free(self):
        closed = jax.make_jaxpr(lambda x: x.sum(axis=0))(
            jnp.zeros((8, 64), jnp.float32))
        r = plan_jaxpr(closed, [PS(None, "tp")], mesh={"tp": 4})
        assert r.collectives == []

    def test_indivisible_dim_is_silently_replicated(self):
        # shape 10 on a 4-way axis cannot shard; the planner must not
        # invent fractional shards (S204 handles the layout complaint).
        closed = jax.make_jaxpr(lambda x: x * 1.5)(
            jnp.zeros((10, 16), jnp.float32))
        r = plan_jaxpr(closed, [PS("tp", None)], mesh={"tp": 4})
        assert r.collectives == []
        assert r.per_chip_peak_hbm_bytes == 2 * 10 * 16 * 4  # replicated


# ---------------------------------------------------------------------------
# diagnostics S205–S208 / H110
# ---------------------------------------------------------------------------

class TestPlanDiagnostics:
    def _matmul_jaxpr(self):
        return jax.make_jaxpr(lambda x, w: x @ w)(
            jnp.zeros((8, 64), jnp.float32), jnp.zeros((64, 32), jnp.float32))

    def test_s205_below_threshold_stays_silent(self):
        closed = jax.make_jaxpr(lambda x, y: x + y)(
            jnp.zeros((16, 16), jnp.float32), jnp.zeros((16, 16), jnp.float32))
        r = plan_jaxpr(closed, [PS("tp", None), PS(None, "tp")],
                       mesh={"tp": 4}, s205_bytes=1 << 20)
        assert sum(1 for c in r.collectives if not c.planned) == 1
        assert "S205" not in _codes(r.diagnostics)

    def test_s206_replicated_large_param(self):
        closed = jax.make_jaxpr(lambda x: x * 2.0)(
            jnp.zeros((8, 8), jnp.float32))
        r = plan_jaxpr(closed, [PS()], mesh={"data": 2},
                       param_info=[("big.weight", 16 << 20, PS()),
                                   ("sharded.weight", 16 << 20, PS("fsdp")),
                                   ("tiny.weight", 1 << 10, PS())])
        s206 = [d for d in r.diagnostics if d.code == "S206"]
        assert len(s206) == 1  # sharded and tiny params are exempt
        assert "big.weight" in s206[0].message
        assert s206[0].severity == "warning"

    def test_s207_collective_bound_on_slow_wire(self):
        slow = ChipProfile("slowwire", 5e11, 50e9, 8 << 30,
                           ici_bandwidth=1e3, ici_latency=0.0)
        r = plan_jaxpr(self._matmul_jaxpr(),
                       [PS(None, "tp"), PS("tp", None)],
                       mesh={"tp": 4}, chip=slow)
        s207 = [d for d in r.diagnostics if d.code == "S207"]
        assert len(s207) == 1 and s207[0].severity == "error"

    def test_s208_batch_off_data_axis(self):
        r = plan_jaxpr(self._matmul_jaxpr(), [PS(), PS("tp", None)],
                       mesh={"data": 2, "tp": 4},
                       data_inputs=(("x", 0),))
        s208 = [d for d in r.diagnostics if d.code == "S208"]
        assert len(s208) == 1 and s208[0].severity == "warning"
        assert "'x'" in s208[0].message

    def test_s208_skips_batch_one(self):
        closed = jax.make_jaxpr(lambda x: x * 2.0)(
            jnp.zeros((1, 16), jnp.float32))
        r = plan_jaxpr(closed, [PS()], mesh={"data": 2},
                       data_inputs=(("chunk", 0),))
        assert "S208" not in _codes(r.diagnostics)

    def test_h110_per_chip_budget(self):
        r = plan_jaxpr(self._matmul_jaxpr(),
                       [PS(None, "tp"), PS("tp", None)],
                       mesh={"tp": 4}, hbm_budget_bytes=1)
        assert "H110" in _codes(r.errors())

    def test_diagnostics_are_sorted(self):
        slow = ChipProfile("slowwire", 5e11, 50e9, 8 << 30, 1e3, 0.0)
        r = plan_jaxpr(self._matmul_jaxpr(), [PS(), PS("tp", None)],
                       mesh={"data": 2, "tp": 4}, chip=slow,
                       hbm_budget_bytes=1, data_inputs=(("x", 0),))
        codes = _codes(r.diagnostics)
        assert codes == sorted(codes)


# ---------------------------------------------------------------------------
# S204 message contract (satellite: size AND mesh-axis product)
# ---------------------------------------------------------------------------

class TestS204Message:
    def test_single_axis_names_size_and_product(self):
        diags = check_sharding_readiness({"embed": PS("tp", None)},
                                         {"embed": (255, 32)}, {"tp": 4})
        assert _codes(diags) == ["S204"]
        msg = diags[0].message
        assert "size 255" in msg
        assert "tp=4" in msg
        assert "mesh-axis product" in msg

    def test_multi_axis_product_is_spelled_out(self):
        diags = check_sharding_readiness(
            {"embed": PS(("tp", "fsdp"), None)},
            {"embed": (255, 32)}, {"tp": 4, "fsdp": 2})
        msg = diags[0].message
        assert "tp=4 × fsdp=2" in msg
        assert "= 8" in msg


# ---------------------------------------------------------------------------
# canonical llama SpecLayout (satellite: readiness across meshes)
# ---------------------------------------------------------------------------

class TestLlamaSpecLayout:
    # representative per-role shapes from LlamaConfig.tiny()
    # (hidden=64, intermediate=128, vocab=256)
    SHAPES = {
        "embed": (256, 64),
        "lm_head": (64, 256),
        "attn_qkv": (64, 64),
        "attn_out": (64, 64),
        "mlp_in": (64, 128),
        "mlp_out": (128, 64),
        "norm": (64,),
    }

    def test_every_tiny_llama_param_resolves_to_a_role(self):
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

        net = LlamaForCausalLM(LlamaConfig.tiny())
        unresolved = [n for n, _ in net.named_parameters()
                      if llama_param_role(n) is None]
        assert unresolved == []
        specs = llama_param_specs(net)
        assert len(specs) == len(list(net.named_parameters()))
        assert specs["lm_head.weight"] == PS("fsdp", "tp")
        # norm weights replicate
        assert all(specs[n] == PS() for n in specs if "norm" in n)

    @pytest.mark.parametrize("mesh", [
        {"data": 1, "fsdp": 1, "tp": 1},
        {"data": 2, "fsdp": 2, "tp": 2},
        {"data": 4, "fsdp": 8, "tp": 1},
    ])
    def test_layout_passes_readiness_on_mesh(self, mesh):
        diags = check_sharding_readiness(SpecLayout().role_layout(),
                                         self.SHAPES, mesh)
        assert diags == []

    def test_non_divisible_vocab_dim_is_caught(self):
        shapes = dict(self.SHAPES, embed=(255, 64))
        diags = check_sharding_readiness(
            SpecLayout().role_layout(), shapes,
            {"data": 2, "fsdp": 2, "tp": 2})
        assert "S204" in _codes(diags)
        assert any("255" in d.message and "tp=2" in d.message
                   for d in diags)

    def test_unknown_role_raises(self):
        with pytest.raises(KeyError, match="unknown param role"):
            SpecLayout().spec_for_role("conv_stem")

    def test_batch_axis_none_replicates_batch(self):
        assert SpecLayout(batch_axis=None).batch_spec() == PS()
        assert SpecLayout().batch_spec() == PS("data")


# ---------------------------------------------------------------------------
# registered-step audit (what `lint_tpu.py --shardplan` / CI runs)
# ---------------------------------------------------------------------------

class TestAuditShardplan:
    @pytest.fixture(scope="class")
    def reports(self):
        return audit_shardplan()

    def test_covers_all_default_step_kinds(self, reports):
        assert [r.name for r in reports] == [
            "hapi::train_step", "serving::decode_step",
            "serving::prefill_step", "serving::sampled_decode_step",
            "serving::spec_verify_step", "moe::block_step",
            "ring::sp_step"]

    def test_clean_layout_has_no_unplanned_or_errors(self, reports):
        for r in reports:
            assert all(c.planned for c in r.collectives), r.name
            assert r.errors() == [], r.name

    def test_reports_carry_headline_numbers(self, reports):
        for r in reports:
            assert r.per_chip_peak_hbm_bytes > 0
            assert r.comm_bytes > 0
            assert len(r.collectives) > 0
            assert r.n_chips == 8

    def test_train_step_matches_params_by_name(self, reports):
        train = reports[0]
        assert any(k.endswith("q_proj.weight") for k in train.param_specs)
        assert len(train.param_specs) == 21  # every tiny-llama param

    def test_misplaced_batch_layout_is_rejected(self):
        reports = audit_shardplan(layout=SpecLayout(batch_axis="tp"))
        errs = [d for r in reports for d in r.errors()]
        assert "S205" in _codes(errs)

    def test_summary_and_table_render(self, reports):
        for r in reports:
            assert "per-chip peak HBM" in r.summary()
            assert "KiB/chip" in r.table()


# ---------------------------------------------------------------------------
# lint_tpu --shardplan CLI exit-code contract
# ---------------------------------------------------------------------------

class TestShardplanCli:
    def _run(self, *flags):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "lint_tpu.py"),
             "--shardplan", *flags],
            capture_output=True, text=True, env=env, cwd=REPO, timeout=240)

    def test_clean_layout_exits_zero_and_reports(self):
        proc = self._run()
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "per-chip peak HBM" in proc.stdout
        assert "collective byte(s) on the wire" in proc.stdout
        assert "0 error(s)" in proc.stdout

    def test_injected_bad_batch_axis_exits_one(self):
        proc = self._run("--batch-axis", "tp")
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "S205" in proc.stdout


# ---------------------------------------------------------------------------
# opt-in wiring: Model.fit(shardplan=...) / ServingConfig.shardplan
# ---------------------------------------------------------------------------

def _tiny_hapi_model():
    net = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 4))
    model = paddle.Model(net)
    model.prepare(
        paddle.optimizer.SGD(learning_rate=0.1,
                             parameters=net.parameters()),
        nn.CrossEntropyLoss())
    return model


def _batch():
    x = paddle.to_tensor(np.random.randn(8, 16).astype("float32"))
    y = paddle.to_tensor(np.random.randint(0, 4, (8, 1)).astype("int64"))
    return x, y


class TestModelShardplanWiring:
    def test_model_shardplan_returns_report(self):
        model = _tiny_hapi_model()
        x, y = _batch()
        rep = model.shardplan([x], [y])
        assert rep.name == "hapi::train_step"
        assert model.shardplan_report is rep
        assert rep.errors() == []

    def test_fit_shardplan_gate_raises_on_error(self):
        import paddle_tpu.io as io

        class DS(io.Dataset):
            def __len__(self):
                return 16

            def __getitem__(self, i):
                return (np.random.randn(16).astype("float32"),
                        np.random.randint(0, 4, (1,)).astype("int64"))

        loader = io.DataLoader(DS(), batch_size=8)
        model = _tiny_hapi_model()
        model.fit(loader, epochs=1, shardplan=True, verbose=0)
        assert model.shardplan_report is not None

        model = _tiny_hapi_model()
        with pytest.raises(RuntimeError, match="H110"):
            model.fit(loader, epochs=1, verbose=0,
                      shardplan=PlanRequest(hbm_budget_bytes=1))

        # raise_on_error=False demotes the gate to a recorded report
        model = _tiny_hapi_model()
        model.fit(loader, epochs=1, verbose=0,
                  shardplan=PlanRequest(hbm_budget_bytes=1,
                                        raise_on_error=False))
        assert "H110" in _codes(model.shardplan_report.errors())


class TestEngineShardplanWiring:
    def _net(self):
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

        net = LlamaForCausalLM(LlamaConfig.tiny())
        net.eval()
        return net

    def test_engine_startup_plan(self):
        from paddle_tpu.serving import Engine, ServingConfig

        eng = Engine(self._net(), ServingConfig(
            max_batch_size=2, block_size=4, num_blocks=16,
            chunk_tokens=16, shardplan=True))
        assert eng.shardplan_reports is not None
        assert {r.name for r in eng.shardplan_reports} == {
            "serving::decode_step", "serving::prefill_step"}
        for r in eng.shardplan_reports:
            assert r.errors() == []

    def test_engine_raises_on_injected_conflict(self):
        from paddle_tpu.serving import Engine, ServingConfig

        with pytest.raises(ValueError, match="S205"):
            Engine(self._net(), ServingConfig(
                max_batch_size=2, block_size=4, num_blocks=16,
                chunk_tokens=16,
                shardplan=PlanRequest(layout=SpecLayout(batch_axis="tp"),
                                      s205_bytes=1)))

    def test_engine_off_by_default(self):
        from paddle_tpu.serving import Engine, ServingConfig

        eng = Engine(self._net(), ServingConfig(
            max_batch_size=2, block_size=4, num_blocks=16, chunk_tokens=16))
        assert eng.shardplan_reports is None


# ---------------------------------------------------------------------------
# observability gauges
# ---------------------------------------------------------------------------

@pytest.fixture
def telemetry():
    from paddle_tpu import observability as obs

    obs.get_registry().clear()
    prev = obs.enable(True)
    yield obs
    obs.enable(prev)
    obs.get_registry().clear()


class TestShardplanGauges:
    def test_model_shardplan_exports_gauges(self, telemetry):
        model = _tiny_hapi_model()
        x, y = _batch()
        rep = model.shardplan([x], [y])
        reg = telemetry.get_registry()
        assert reg.gauge("shardplan_comm_bytes").value(
            step="hapi::train_step") == rep.comm_bytes
        assert reg.gauge("shardplan_per_chip_peak_hbm_bytes").value(
            step="hapi::train_step") == rep.per_chip_peak_hbm_bytes

    def test_disabled_telemetry_is_a_noop(self):
        from paddle_tpu import observability as obs
        from paddle_tpu.analysis.shardplan import export_plan_gauges

        assert not obs.enabled()
        closed = jax.make_jaxpr(lambda x: x * 2.0)(
            jnp.zeros((4, 4), jnp.float32))
        export_plan_gauges(plan_jaxpr(closed, [PS()], mesh={"tp": 2}))
        assert obs.get_registry().names() == []


# ---------------------------------------------------------------------------
# ICI profile satellite: CHIPS carry wire specs, roofline uses them
# ---------------------------------------------------------------------------

class TestIciProfiles:
    def test_every_chip_has_wire_numbers(self):
        for name, chip in CHIPS.items():
            assert chip.ici_bandwidth > 0, name
            assert chip.ici_latency >= 0, name
        # v5p ICI (4800 Gbps) outruns v5e (1600 Gbps aggregate)
        assert CHIPS["v5p"].ici_bandwidth > CHIPS["v5e"].ici_bandwidth

    def test_estimate_collective_time(self):
        from paddle_tpu.analysis.xray import estimate_collective_time

        v4 = CHIPS["v4"]
        assert estimate_collective_time(300e9, v4) == pytest.approx(
            1.0 + v4.ici_latency)

    def test_plan_summary_scales_with_chip(self):
        closed = jax.make_jaxpr(lambda x, w: x @ w)(
            jnp.zeros((8, 64), jnp.float32), jnp.zeros((64, 32), jnp.float32))
        specs = [PS(None, "tp"), PS("tp", None)]
        slow = plan_jaxpr(closed, specs, mesh={"tp": 4}, chip="v5e")
        fast = plan_jaxpr(closed, specs, mesh={"tp": 4}, chip="v5p")
        assert fast.comm_time_s < slow.comm_time_s
