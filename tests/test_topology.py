"""Multi-host topology planning (paddle_tpu.analysis.topology + the
shardplan wiring, ISSUE 12).

Golden-value contracts first: the hierarchical all-reduce decomposition
(RS(ici) + AR(dcn) + AG(ici)) with hand-computed per-phase bytes and
link-priced times, and the public-spec DCN figures on every ChipProfile.
Then the split/validate rules, per-kind phase shapes, the S213/S214/S215
diagnostics, the layout recommender ranking, the `--hosts/--json` CLI
contract, the reconcile-vs-topology mismatch guard, and the H112
device-count hazard scanner.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.analysis import audit_shardplan, plan_jaxpr
from paddle_tpu.analysis.hazards import (ERROR, WARNING,
                                         scan_device_count_assumptions)
from paddle_tpu.analysis.shardplan import recommend_layouts
from paddle_tpu.analysis.topology import (Topology, enumerate_topologies,
                                          format_recommendations,
                                          rank_layouts)
from paddle_tpu.analysis.xray import CHIPS, ChipProfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _codes(diags):
    return [d.code for d in diags]


def _matmul_plan(mesh, topology, chip="cpu", step_kind=None):
    """x[8,64] P(None,'tp') @ w[64,32] P('tp',None): both contraction
    sides sharded on 'tp' — one planned all-reduce of the f32 [8,32]
    output (payload 1024 B), the flat golden from test_shardplan."""
    f = lambda x, w: x @ w  # noqa: E731
    closed = jax.make_jaxpr(f)(jnp.zeros((8, 64), jnp.float32),
                               jnp.zeros((64, 32), jnp.float32))
    return plan_jaxpr(closed, [PS(None, "tp"), PS("tp", None)],
                      mesh=mesh, name="golden", chip=chip,
                      topology=topology, step_kind=step_kind)


# ---------------------------------------------------------------------------
# golden: hierarchical all-reduce decomposition, hand-computed
# ---------------------------------------------------------------------------

class TestGoldenHierarchicalAllReduce:
    """tp=8 over 2 hosts × (4,) chips, tp pinned to DCN: the flat
    1024 B all-reduce (2·1024·7/8 = 1792 B flat wire) decomposes as

    - reduce_scatter  ici  payload 1024, ×(4−1)/4        = 768 B
    - all_reduce      dcn  payload 1024/4, ×2·(2−1)/2    = 256 B
    - all_gather      ici  payload 1024, ×(4−1)/4        = 768 B

    The DCN leg runs on the S/n_i shard the intra-host reduce_scatter
    left behind — the point of the hierarchical lowering.
    """

    TOPO = Topology(hosts=2, chips_per_host=(4,),
                    axis_levels={"tp": "dcn"})

    @pytest.fixture(scope="class")
    def report(self):
        return _matmul_plan({"tp": 8}, self.TOPO)

    def test_three_phases_in_lowering_order(self, report):
        got = [(c.kind, c.level, c.axes) for c in report.collectives]
        assert got == [
            ("reduce_scatter", "ici", ("tp",)),
            ("all_reduce", "dcn", ("tp",)),
            ("all_gather", "ici", ("tp",)),
        ]

    def test_phase_bytes_golden(self, report):
        rs, ar, ag = report.collectives
        assert (rs.payload_bytes, rs.bytes_moved) == (1024, 768)
        assert (ar.payload_bytes, ar.bytes_moved) == (256, 256)
        assert (ag.payload_bytes, ag.bytes_moved) == (1024, 768)
        assert report.ici_comm_bytes == 1536
        assert report.dcn_comm_bytes == 256

    def test_flat_inventory_retained_for_repricing(self, report):
        # the recommender reprices the raw propagation output without
        # re-tracing, so the flat collective must survive decomposition
        (flat,) = report.flat_collectives
        assert flat.kind == "all_reduce"
        assert flat.payload_bytes == 1024
        assert flat.bytes_moved == 1792  # 2·1024·(8−1)/8 on a flat ring

    def test_phase_times_use_matching_link_profile(self, report):
        cpu = CHIPS["cpu"]
        rs, ar, ag = report.collectives
        assert rs.time_s == pytest.approx(
            768 / cpu.ici_bandwidth + cpu.ici_latency)
        assert ar.time_s == pytest.approx(
            256 / cpu.dcn_bandwidth + cpu.dcn_latency)
        assert ag.time_s == pytest.approx(
            768 / cpu.ici_bandwidth + cpu.ici_latency)

    def test_dcn_time_responds_to_dcn_bandwidth_ici_does_not(self):
        # same chip except DCN half as fast: only the DCN phase moves
        fast = ChipProfile("a", 5e11, 50e9, 8 << 30, 200e9, 0.0,
                           20e9, 1e-6)
        slow = ChipProfile("b", 5e11, 50e9, 8 << 30, 200e9, 0.0,
                           10e9, 1e-6)
        r_fast = _matmul_plan({"tp": 8}, self.TOPO, chip=fast)
        r_slow = _matmul_plan({"tp": 8}, self.TOPO, chip=slow)
        assert r_slow.dcn_comm_time_s == pytest.approx(
            256 / 10e9 + 1e-6)
        assert r_slow.dcn_comm_time_s > r_fast.dcn_comm_time_s
        assert r_slow.ici_comm_time_s == r_fast.ici_comm_time_s

    def test_summary_names_hosts_and_link_split(self, report):
        s = report.summary()
        assert "2 host(s) × 4 chips" in s
        assert "ICI" in s and "DCN" in s
        assert "per-host peak HBM" in s

    def test_per_host_budget_aggregates(self, report):
        assert report.chips_per_host_count == 4
        assert report.per_host_peak_hbm_bytes == \
            4 * report.per_chip_peak_hbm_bytes
        assert report.dcn_bytes_per_host == 4 * 256

    def test_table_has_link_column(self, report):
        t = report.table()
        assert "link" in t
        assert "dcn" in t and "ici" in t


# ---------------------------------------------------------------------------
# golden: public-spec DCN figures on the chip profiles
# ---------------------------------------------------------------------------

class TestChipProfileDcnGoldens:
    """Per-chip DCN bandwidth = host NIC line rate / chips-per-host / 8
    bits — the figures below follow the public Cloud TPU system specs
    (v4: 200 Gbps NIC, 4 chips/host; v5e: 100 Gbps, 4 chips/host;
    v5p/v6e: 400 Gbps, 4 chips/host).  Latency is the canonical ~10 µs
    cross-host RTT used in multislice planning docs."""

    def test_v4_dcn(self):
        # 200 Gbps / 8 bits / 4 chips = 6.25 GB/s per chip
        assert CHIPS["v4"].dcn_bandwidth == 6.25e9
        assert CHIPS["v4"].dcn_latency == 1e-5

    def test_v5e_dcn(self):
        # 100 Gbps / 8 / 4 = 3.125 GB/s per chip
        assert CHIPS["v5e"].dcn_bandwidth == 3.125e9
        assert CHIPS["v5e"].dcn_latency == 1e-5

    def test_v5p_dcn(self):
        # 400 Gbps / 8 / 4 = 12.5 GB/s per chip
        assert CHIPS["v5p"].dcn_bandwidth == 12.5e9
        assert CHIPS["v5p"].dcn_latency == 1e-5

    def test_v6e_dcn(self):
        # 400 Gbps / 8 / 4 = 12.5 GB/s per chip
        assert CHIPS["v6e"].dcn_bandwidth == 12.5e9
        assert CHIPS["v6e"].dcn_latency == 1e-5

    def test_cpu_is_loopback_but_strictly_slower_than_ici(self):
        # emulated multi-host on one dev box: DCN crosses no real NIC,
        # but must stay strictly worse than ICI so decomposition and
        # the S213-S215 gates still order the links correctly
        cpu = CHIPS["cpu"]
        assert cpu.dcn_bandwidth == 25e9
        assert cpu.dcn_latency == 2e-7
        assert cpu.dcn_bandwidth < cpu.ici_bandwidth
        assert cpu.dcn_latency > cpu.ici_latency

    def test_every_profile_orders_dcn_below_ici(self):
        for name, chip in CHIPS.items():
            assert chip.dcn_bandwidth < chip.ici_bandwidth, name


# ---------------------------------------------------------------------------
# Topology: splits, validate, level_of
# ---------------------------------------------------------------------------

class TestTopologySplits:
    MESH = {"data": 2, "fsdp": 2, "tp": 2}

    def test_default_walk_puts_first_axis_on_dcn(self):
        topo = Topology(hosts=2, chips_per_host=(2, 2))
        assert topo.splits(self.MESH) == {
            "data": (1, 2), "fsdp": (2, 1), "tp": (2, 1)}
        assert topo.level_of("data", self.MESH) == "dcn"
        assert topo.level_of("tp", self.MESH) == "ici"

    def test_pinned_axis_consumes_dcn_capacity_first(self):
        topo = Topology(hosts=2, chips_per_host=(2, 2),
                        axis_levels={"tp": "dcn"})
        assert topo.splits(self.MESH) == {
            "data": (2, 1), "fsdp": (2, 1), "tp": (1, 2)}

    def test_axis_larger_than_hosts_splits(self):
        # an 8-way axis over 2 hosts: 2 of its factors cross hosts,
        # the other 4 stay intra-host
        topo = Topology(hosts=2, chips_per_host=(4,))
        assert topo.splits({"tp": 8}) == {"tp": (4, 2)}

    def test_single_host_everything_ici(self):
        topo = Topology(hosts=1, chips_per_host=(2, 2, 2))
        assert topo.splits(self.MESH) == {
            "data": (2, 1), "fsdp": (2, 1), "tp": (2, 1)}

    def test_validate_rejects_chip_count_mismatch(self):
        with pytest.raises(ValueError, match="chips"):
            Topology(hosts=2, chips_per_host=(4,)).validate({"tp": 4})

    def test_validate_rejects_assignment_not_covering_hosts(self):
        topo = Topology(hosts=2, chips_per_host=(2, 2),
                        axis_levels={"data": "ici", "fsdp": "ici",
                                     "tp": "ici"})
        with pytest.raises(ValueError, match="host"):
            topo.validate(self.MESH)

    def test_constructor_rejects_bad_inputs(self):
        with pytest.raises(ValueError, match="hosts"):
            Topology(hosts=0)
        with pytest.raises(ValueError, match="ici"):
            Topology(axis_levels={"tp": "wan"})


class TestPhaseShapes:
    MESH = {"data": 2, "tp": 4}
    TOPO = Topology(hosts=2, chips_per_host=(4,),
                    axis_levels={"data": "dcn"})

    def test_pure_ici_axis_single_phase(self):
        (ph,) = self.TOPO.phases("all_reduce", ("tp",), 1024, self.MESH)
        assert (ph.level, ph.factor) == ("ici", 2 * 3 / 4)

    def test_pure_dcn_axis_single_phase(self):
        (ph,) = self.TOPO.phases("all_gather", ("data",), 1024, self.MESH)
        assert (ph.level, ph.factor) == ("dcn", 1 / 2)

    def test_all_gather_dcn_leg_runs_on_smallest_shard(self):
        # axes spanning both levels: the DCN gather moves the S/n_i
        # per-host shard first, then ICI broadcasts the full payload
        dcn, ici = self.TOPO.phases("all_gather", ("data", "tp"),
                                    1024, self.MESH)
        assert (dcn.level, dcn.payload_bytes, dcn.factor) == \
            ("dcn", 256, 1 / 2)
        assert (ici.level, ici.payload_bytes, ici.factor) == \
            ("ici", 1024, 3 / 4)

    def test_reduce_scatter_ici_first_then_dcn_shard(self):
        ici, dcn = self.TOPO.phases("reduce_scatter", ("data", "tp"),
                                    1024, self.MESH)
        assert (ici.level, ici.payload_bytes) == ("ici", 1024)
        assert (dcn.level, dcn.payload_bytes) == ("dcn", 256)

    def test_all_to_all_fractions_by_level(self):
        dcn, ici = self.TOPO.phases("all_to_all", ("data", "tp"),
                                    1024, self.MESH)
        assert (dcn.level, dcn.factor) == ("dcn", 1 / 2)
        assert (ici.level, ici.factor) == ("ici", 3 / 4)

    def test_ppermute_gated_by_slowest_edge(self):
        # any DCN factor on the axis makes the synchronous ring hop a
        # DCN hop end to end; an all-ICI axis stays ICI
        (ph,) = self.TOPO.phases("ppermute", ("data",), 512, self.MESH,
                                 factor=1.0)
        assert (ph.level, ph.factor) == ("dcn", 1.0)
        (ph,) = self.TOPO.phases("ppermute", ("tp",), 512, self.MESH,
                                 factor=1.0)
        assert ph.level == "ici"

    def test_unknown_kind_prices_conservatively_on_dcn(self):
        (ph,) = self.TOPO.phases("mystery", ("data", "tp"), 1024,
                                 self.MESH)
        assert ph.level == "dcn"


# ---------------------------------------------------------------------------
# diagnostics: S213 / S214 / S215
# ---------------------------------------------------------------------------

class TestDcnDiagnostics:
    def test_s213_decode_with_tp_on_dcn(self):
        topo = Topology(hosts=2, chips_per_host=(2, 2),
                        axis_levels={"tp": "dcn"})
        (rep,) = audit_shardplan(steps=("decode",), topology=topo)
        errs = [d for d in rep.diagnostics if d.code == "S213"]
        assert len(errs) == 1
        assert errs[0].severity == ERROR
        assert "tp" in errs[0].message
        # the avoidable assignment also trips the S214 swap suggestion
        assert "S214" in _codes(rep.diagnostics)

    def test_s213_quiet_on_default_assignment(self):
        # the default walk crosses hosts on the batch axis, which
        # decode only touches with sub-floor control reduces
        topo = Topology(hosts=2, chips_per_host=(2, 2))
        (rep,) = audit_shardplan(steps=("decode",), topology=topo)
        assert "S213" not in _codes(rep.diagnostics)

    def test_s213_only_in_latency_critical_step_kinds(self):
        # the same tp-on-DCN layout in the TRAIN step is throughput
        # work, not a request critical path — no S213
        topo = Topology(hosts=2, chips_per_host=(2, 2),
                        axis_levels={"tp": "dcn"})
        (rep,) = audit_shardplan(steps=("train",), topology=topo)
        assert "S213" not in _codes(rep.diagnostics)

    def test_s215_unhideable_dcn_phase(self):
        # a pathologically slow DCN link: the 256 B inter-host
        # all-reduce can never hide behind the tiny matmul's compute
        chip = ChipProfile("slow-dcn", 5e11, 50e9, 8 << 30, 200e9, 0.0,
                           1e6, 1e-3)
        rep = _matmul_plan({"tp": 8},
                           Topology(hosts=2, chips_per_host=(4,),
                                    axis_levels={"tp": "dcn"}),
                           chip=chip)
        s215 = [d for d in rep.diagnostics if d.code == "S215"]
        assert len(s215) == 1
        assert s215[0].severity == WARNING
        assert "all_reduce" in s215[0].message

    def test_s215_quiet_when_dcn_hides_behind_compute(self):
        # a compute-bound profile: the matmul's ~4 µs step window
        # comfortably hides the 256 B / ~0.2 µs inter-host leg
        chip = ChipProfile("slow-compute", 1e9, 1e9, 8 << 30, 200e9,
                           0.0, 25e9, 2e-7)
        rep = _matmul_plan({"tp": 8},
                           Topology(hosts=2, chips_per_host=(4,),
                                    axis_levels={"tp": "dcn"}),
                           chip=chip)
        assert "S215" not in _codes(rep.diagnostics)
        assert "S207" not in _codes(rep.diagnostics)

    def test_s207_message_is_level_aware(self):
        chip = ChipProfile("slow-dcn", 5e11, 50e9, 8 << 30, 200e9, 0.0,
                           1e6, 1e-3)
        rep = _matmul_plan({"tp": 8},
                           Topology(hosts=2, chips_per_host=(4,),
                                    axis_levels={"tp": "dcn"}),
                           chip=chip)
        s207 = [d for d in rep.diagnostics if d.code == "S207"]
        assert s207 and "DCN" in s207[0].message


# ---------------------------------------------------------------------------
# end-to-end audit + gauges on the emulated 2-host topology
# ---------------------------------------------------------------------------

class TestMultiHostAudit:
    def test_all_default_steps_plan_clean(self):
        topo = Topology(hosts=2, chips_per_host=(2, 2))
        reports = audit_shardplan(topology=topo)
        assert len(reports) == 7
        for r in reports:
            assert r.errors() == [], (r.name, [str(d) for d in r.errors()])
            assert all(c.planned for c in r.collectives), r.name
            assert r.topology is topo
        # host-crossing traffic exists and is priced on the slow link
        assert any(r.dcn_comm_bytes > 0 for r in reports)

    def test_ici_dcn_gauges_exported(self):
        import paddle_tpu.observability as obs
        from paddle_tpu.analysis.shardplan import export_plan_gauges

        topo = Topology(hosts=2, chips_per_host=(2, 2))
        (rep,) = audit_shardplan(steps=("train",), topology=topo)
        obs.enable()
        try:
            export_plan_gauges(rep)
            reg = obs.get_registry()
            assert reg.gauge("shardplan_ici_comm_bytes").value(
                step=rep.name) == pytest.approx(rep.ici_comm_bytes)
            assert reg.gauge("shardplan_dcn_comm_bytes").value(
                step=rep.name) == pytest.approx(rep.dcn_comm_bytes)
        finally:
            obs.disable()

    def test_to_json_schema(self):
        topo = Topology(hosts=2, chips_per_host=(2, 2))
        (rep,) = audit_shardplan(steps=("train",), topology=topo)
        doc = json.loads(json.dumps(rep.to_json()))  # round-trips
        assert doc["hosts"] == 2
        assert doc["chips_per_host"] == [2, 2]
        assert set(doc["wire_bytes"]) == {"ici", "dcn"}
        assert set(doc["comm_time_s"]) == {"ici", "dcn"}
        assert doc["per_host_peak_hbm_bytes"] == \
            4 * doc["per_chip_peak_hbm_bytes"]
        assert all({"kind", "level", "axes"} <= set(c)
                   for c in doc["collectives"])


# ---------------------------------------------------------------------------
# layout recommender
# ---------------------------------------------------------------------------

class TestRecommender:
    def test_decode_ranks_tp_on_ici_above_tp_on_dcn(self):
        # the acceptance contract: for the canonical llama decode step
        # the best layout keeps tp inside the host (batch axis crosses)
        # and every layout putting tp on DCN ranks strictly below it
        topo = Topology(hosts=2, chips_per_host=(2, 2))
        (rep,) = audit_shardplan(steps=("decode",), topology=topo)
        ranked = recommend_layouts(rep)
        assert ranked[0].dcn_axes == ("data",)
        best_tp_dcn = next(i for i, r in enumerate(ranked)
                           if "tp" in r.dcn_axes)
        assert best_tp_dcn > 0
        assert ranked[best_tp_dcn].comm_time_s > ranked[0].comm_time_s

    def test_ranking_is_by_comm_time(self):
        topo = Topology(hosts=2, chips_per_host=(2, 2))
        (rep,) = audit_shardplan(steps=("decode",), topology=topo)
        ranked = recommend_layouts(rep)
        times = [r.comm_time_s for r in ranked]
        assert times == sorted(times)

    def test_enumerate_skips_degenerate_and_dedups(self):
        topos = enumerate_topologies({"data": 2, "fsdp": 2, "tp": 2},
                                     hosts=2, chips_per_host=(2, 2))
        keys = [tuple(sorted(a for a, lvl in
                             ((ax, t.axis_levels.get(ax, "ici"))
                              for ax in ("data", "fsdp", "tp"))
                             if lvl == "dcn" and t.splits(
                                 {"data": 2, "fsdp": 2, "tp": 2}
                             )[a][1] > 1))
                for t in topos]
        assert len(keys) == len(set(keys))
        # one single-axis assignment per axis (2-host fleet, size-2 axes)
        singles = [k for k in keys if len(k) == 1]
        assert sorted(singles) == [("data",), ("fsdp",), ("tp",)]

    def test_rank_layouts_reprices_flat_inventory(self):
        rep = _matmul_plan({"tp": 8},
                           Topology(hosts=2, chips_per_host=(4,),
                                    axis_levels={"tp": "dcn"}))
        ranked = rank_layouts(rep.flat_collectives, {"tp": 8},
                              CHIPS["cpu"], hosts=2,
                              chips_per_host=(4,))
        # only one axis exists, so the single valid layout reproduces
        # the decomposed plan exactly
        (layout,) = ranked
        assert layout.dcn_axes == ("tp",)
        assert layout.ici_bytes == rep.ici_comm_bytes
        assert layout.dcn_bytes == rep.dcn_comm_bytes

    def test_format_recommendations_table(self):
        topo = Topology(hosts=2, chips_per_host=(2, 2))
        (rep,) = audit_shardplan(steps=("decode",), topology=topo)
        table = format_recommendations(recommend_layouts(rep))
        assert "rank" in table and "DCN KiB" in table
        assert "data" in table

    def test_recommend_requires_hosts_or_topology(self):
        (rep,) = audit_shardplan(steps=("decode",))
        with pytest.raises(ValueError, match="hosts"):
            recommend_layouts(rep)


# ---------------------------------------------------------------------------
# lint_tpu --shardplan --hosts CLI contract (+ --json schema)
# ---------------------------------------------------------------------------

class TestTopologyCli:
    def _run(self, *flags):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "lint_tpu.py"),
             "--shardplan", *flags],
            capture_output=True, text=True, env=env, cwd=REPO, timeout=240)

    def test_two_host_audit_exits_zero_and_recommends(self):
        # one subprocess covers the exit-0 contract, the host-tagged
        # link-split output, AND the --recommend table (the full
        # five-step × 2-host audit runs in-process in
        # TestMultiHostAudit and as a tools/ci.sh stage)
        proc = self._run("--hosts", "2", "--chips-per-host", "2,2",
                         "--steps", "decode", "--recommend")
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "host(s)" in proc.stdout
        assert "DCN" in proc.stdout
        assert "0 error(s)" in proc.stdout
        assert "layout recommendations" in proc.stdout
        assert "dcn axes" in proc.stdout

    def test_injected_tp_on_dcn_exits_one_with_s213(self):
        proc = self._run("--hosts", "2", "--dcn-axes", "tp",
                         "--steps", "decode")
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "S213" in proc.stdout

    def test_json_reports_are_machine_readable(self):
        proc = self._run("--hosts", "2", "--steps", "train", "--json")
        assert proc.returncode == 0, proc.stdout + proc.stderr
        (doc,) = json.loads(proc.stdout)
        assert doc["hosts"] == 2
        assert set(doc["wire_bytes"]) == {"ici", "dcn"}
        assert isinstance(doc["collectives"], list)
        assert isinstance(doc["diagnostics"], list)

    def test_topology_flags_require_hosts(self):
        proc = self._run("--recommend")
        assert proc.returncode == 2
        assert "--hosts" in proc.stderr


# ---------------------------------------------------------------------------
# reconcile-vs-topology mismatch: multi-host plan on a single-host runtime
# ---------------------------------------------------------------------------

class TestReconcileTopologyMismatch:
    SEQ = 16

    @pytest.fixture(autouse=True)
    def _fresh_registry(self):
        from paddle_tpu.distributed import executor as ex_mod

        yield
        ex = ex_mod.current_executor()
        if ex is not None:
            ex.close()

    def test_reconcile_train_rejects_multi_host_plan(self):
        from paddle_tpu.distributed.executor import MeshExecutor
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

        paddle.seed(0)
        cfg = LlamaConfig.tiny(max_position_embeddings=self.SEQ)
        net = LlamaForCausalLM(cfg)
        model = paddle.Model(net)
        ex = MeshExecutor({"data": 2, "fsdp": 2, "tp": 2},
                          topology=Topology(hosts=2,
                                            chips_per_host=(2, 2)))

        def loss_fn(logits, labels):
            vocab = logits.shape[-1]
            return nn.functional.cross_entropy(
                logits.reshape([-1, vocab]), labels.reshape([-1]))

        model.prepare(paddle.optimizer.AdamW(
            3e-4, parameters=net.parameters()), loss_fn, mesh=ex)
        toks = np.random.RandomState(0).randint(
            0, cfg.vocab_size, (4, self.SEQ)).astype(np.int32)
        model.train_batch([toks], [toks.astype(np.int64)])
        with pytest.raises(RuntimeError, match="2-host"):
            ex.reconcile_train(model, [toks], [toks.astype(np.int64)])
        ex.close()

    def test_reconcile_mesh_rejects_multi_host_plan(self):
        from paddle_tpu.distributed.executor import MeshExecutor
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.serving import Engine, ServingConfig

        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny())
        model.eval()
        ex = MeshExecutor({"data": 2, "fsdp": 2, "tp": 2},
                          topology=Topology(hosts=2,
                                            chips_per_host=(2, 2)))
        eng = Engine(model, ServingConfig(max_batch_size=2, block_size=4,
                                          num_blocks=16, mesh=ex))
        with pytest.raises(RuntimeError, match="2-host"):
            eng.reconcile_mesh()
        ex.close()


# ---------------------------------------------------------------------------
# H112: single-process device-count assumption scanner
# ---------------------------------------------------------------------------

class TestH112Scanner:
    def _scan(self, tmp_path, src):
        f = tmp_path / "mod.py"
        f.write_text(textwrap.dedent(src))
        return scan_device_count_assumptions(str(f))

    def test_global_device_count_warns(self, tmp_path):
        diags = self._scan(tmp_path, """\
            import jax
            n = jax.device_count()
        """)
        assert _codes(diags) == ["H112"]
        assert diags[0].severity == WARNING
        assert "local_device_count" in diags[0].message

    def test_len_jax_devices_warns(self, tmp_path):
        diags = self._scan(tmp_path, """\
            import jax
            n = len(jax.devices())
        """)
        assert _codes(diags) == ["H112"]
        assert diags[0].severity == WARNING

    def test_local_variants_are_clean(self, tmp_path):
        diags = self._scan(tmp_path, """\
            import jax
            n = jax.local_device_count()
            m = len(jax.local_devices())
        """)
        assert diags == []

    def test_hardcoded_mesh_ctor_count_is_error(self, tmp_path):
        diags = self._scan(tmp_path, """\
            from jax.sharding import Mesh
            def build(devs):
                return Mesh(devs.reshape(2, 4), ("data", "tp"))
        """)
        errs = [d for d in diags if d.severity == ERROR]
        # the reshape literals surface via the ctor's positional args
        assert not errs
        diags = self._scan(tmp_path, """\
            from paddle_tpu.distributed import init_mesh
            mesh = init_mesh((4, 2), ("data", "tp"))
        """)
        errs = [d for d in diags if d.severity == ERROR]
        assert len(errs) == 1
        assert "[2, 4]" in errs[0].message

    def test_line_suppression(self, tmp_path):
        diags = self._scan(tmp_path, """\
            import jax
            n = jax.device_count()  # lint-tpu: disable=H112
        """)
        assert diags == []

    def test_file_suppression(self, tmp_path):
        diags = self._scan(tmp_path, """\
            # lint-tpu: disable-file=H112
            import jax
            n = jax.device_count()
            mesh = init_mesh((4, 2))
        """)
        assert diags == []

    def test_repo_is_clean(self):
        diags = scan_device_count_assumptions(
            [os.path.join(REPO, "paddle_tpu"),
             os.path.join(REPO, "examples")])
        assert diags == [], [str(d) for d in diags]
