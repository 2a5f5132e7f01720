"""paddle_tpu.analysis.xray — jaxpr-level program X-ray.

ISSUE 6 done bar lives here: golden FLOP/byte/peak-HBM values on a tiny
matmul+elementwise program, H108 (missing donation) firing on an
un-donated train-step clone and staying silent on the donated one, H109
(host round-trip) on a pure_callback step, S201–S204 sharding-readiness
rejections, jaxpr- and AST-level H103 string-dtype spellings, the
deterministic diagnostic ordering, and the lint_tpu CLI exit-code
contract the `lint` CI stage gates on.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import analysis
from paddle_tpu.analysis import astlint, hazards, xray

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _codes(diags):
    return [d.code for d in diags]


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


# ---------------------------------------------------------------------------
# golden cost model values (satellite: golden-value xray cost tests)
# ---------------------------------------------------------------------------

class TestGoldenCosts:
    """Exact FLOP / byte / peak-HBM values on f(a, b) = max(a @ b, 0)
    with a:[128,64] f32, b:[64,32] f32 — small enough to count by hand.
    """

    def _report(self, **kw):
        def step(a, b):
            return jnp.maximum(a @ b, 0.0)

        return xray.analyze(step, [_sds((128, 64)), _sds((64, 32))],
                            chip="cpu", **kw)

    def test_dot_general_flops(self):
        report = self._report()
        by_prim = {o.primitive: o for o in report.ops}
        # 2 * m * k * n = 2 * 128 * 64 * 32
        assert by_prim["dot_general"].flops == 2 * 128 * 64 * 32 == 524288

    def test_peak_hbm_is_sum_of_live_buffers(self):
        # a + b + out all live at once: 128*64*4 + 64*32*4 + 128*32*4
        report = self._report()
        assert report.peak_hbm_bytes == 32768 + 8192 + 16384 == 57344

    def test_elementwise_flops_and_bytes(self):
        report = self._report()
        by_prim = {o.primitive: o for o in report.ops}
        m = by_prim["max"]
        # one output element per compare; the scalar 0.0 is a Literal
        # (0 bytes), so traffic = read a@b + write result
        assert m.flops == 128 * 32
        assert m.bytes == 2 * 128 * 32 * 4

    def test_report_totals_and_table(self):
        report = self._report()
        assert report.flops == sum(o.flops for o in report.ops)
        assert report.arithmetic_intensity > 0
        assert report.n_eqns == 2
        assert "dot_general" in report.table()
        assert "FLOP/B" in report.table()
        assert "[xray]" in report.summary()

    def test_transcendental_weighting(self):
        def step(x):
            return jnp.exp(x)

        report = xray.analyze(step, [_sds((64,))], chip="cpu")
        by_prim = {o.primitive: o for o in report.ops}
        assert by_prim["exp"].flops == 10 * 64  # 10x elementwise weight

    def test_movement_ops_are_zero_flop(self):
        def step(x):
            return jnp.reshape(x, (32, 2)).T

        report = xray.analyze(step, [_sds((64,))], chip="cpu")
        assert report.flops == 0
        assert report.bytes > 0

    def test_scan_multiplies_costs_by_length(self):
        def body(c, x):
            return c + x, c

        def step(xs):
            return jax.lax.scan(body, jnp.zeros(8), xs)

        r1 = xray.analyze(step, [_sds((4, 8))], chip="cpu")
        r2 = xray.analyze(step, [_sds((16, 8))], chip="cpu")
        add1 = {o.primitive: o for o in r1.ops}["add"]
        add2 = {o.primitive: o for o in r2.ops}["add"]
        assert add2.flops == 4 * add1.flops

    def test_roofline_bound_classification(self):
        cpu = xray.CHIPS["cpu"]
        hi = xray.OpCost("dot_general", 1, flops=1e9, bytes=1e6)
        lo = xray.OpCost("add", 1, flops=1e3, bytes=1e6)
        assert hi.bound(cpu) == "compute"
        assert lo.bound(cpu) == "memory"

    def test_hbm_budget_violation_H110(self):
        report = self._report(hbm_budget_bytes=1024)
        assert "H110" in _codes(report.errors())
        assert "budget" in report.summary()


# ---------------------------------------------------------------------------
# H108 missing donation / H109 host round-trip / jaxpr H103
# ---------------------------------------------------------------------------

class TestJaxprHazards:
    def test_H108_fires_on_undonated_matching_output(self):
        def step(w, x):
            return w - 0.01 * x, jnp.sum(x)

        report = xray.analyze(step, [_sds((64, 64)), _sds((64, 64))],
                              chip="cpu", min_donation_bytes=1024)
        h108 = [d for d in report.hazards if d.code == "H108"]
        assert len(h108) == 1
        assert h108[0].severity == "warning"
        assert "donate" in h108[0].message

    def test_H108_silent_when_donated(self):
        # x is [64] (tiny, broadcast): only w could alias the output
        step = jax.jit(lambda w, x: (w - 0.01 * x, jnp.sum(x)),
                       donate_argnums=(0,))
        report = xray.analyze(step, [_sds((64, 64)), _sds((64,))],
                              chip="cpu", min_donation_bytes=1024)
        assert report.donated[0] is True
        assert "H108" not in _codes(report.hazards)

    def test_H108_silent_below_min_bytes(self):
        def step(w, x):
            return w - 0.01 * x

        report = xray.analyze(step, [_sds((8, 8)), _sds((8, 8))],
                              chip="cpu")  # default 1 MiB floor
        assert "H108" not in _codes(report.hazards)

    def test_H108_silent_on_passthrough(self):
        def step(w, x):
            return w, jnp.sum(x)  # w returned as-is: aliasing is free

        report = xray.analyze(step, [_sds((64, 64)), _sds((8,))],
                              chip="cpu", min_donation_bytes=1024)
        assert "H108" not in _codes(report.hazards)

    def test_jit_donation_mask_recovered_from_pjit_eqn(self):
        step = jax.jit(lambda w, x: w + x, donate_argnums=(0,))
        report = xray.analyze(step, [_sds((4,)), _sds((4,))], chip="cpu")
        assert report.donated == (True, False)

    def test_H109_pure_callback_is_error(self):
        def step(x):
            y = jax.pure_callback(
                lambda v: np.asarray(v) * 2, _sds((8,)), x)
            return y + 1.0

        report = xray.analyze(step, [_sds((8,))], chip="cpu")
        h109 = [d for d in report.hazards if d.code == "H109"]
        assert len(h109) == 1 and h109[0].severity == "error"
        assert report.errors()

    def test_H109_debug_callback_is_warning(self):
        def step(x):
            jax.debug.print("x sum = {}", jnp.sum(x))
            return x + 1.0

        report = xray.analyze(step, [_sds((8,))], chip="cpu")
        h109 = [d for d in report.hazards if d.code == "H109"]
        assert h109 and all(d.severity == "warning" for d in h109)
        assert not report.errors()

    def test_H103_jaxpr_level_f64_output(self):
        jax.config.update("jax_enable_x64", True)
        try:
            def step(x):
                return x.astype("float64") * 2.0

            report = xray.analyze(step, [_sds((8,))], chip="cpu")
            assert "H103" in _codes(report.errors())
        finally:
            jax.config.update("jax_enable_x64", False)

    def test_clean_program_has_no_hazards(self):
        def step(x):
            return jnp.tanh(x) @ jnp.ones((8, 4), jnp.float32)

        report = xray.analyze(step, [_sds((2, 8))], chip="cpu")
        assert report.hazards == []


# ---------------------------------------------------------------------------
# AST-level H103 string-dtype spellings (satellite 3: one test per
# spelling)
# ---------------------------------------------------------------------------

class TestAstH103StringDtypes:
    def _scan(self, fn):
        return [d for d in hazards.scan_function(fn) if d.code == "H103"]

    def test_dtype_kwarg_float64(self):
        def f(x):
            return paddle.zeros([4], dtype="float64") + x

        assert self._scan(f)

    def test_dtype_kwarg_double(self):
        def f(x):
            return paddle.ones([4], dtype="double") + x

        assert self._scan(f)

    def test_astype_float64_string(self):
        def f(x):
            return x.astype("float64")

        assert self._scan(f)

    def test_astype_double_string(self):
        def f(x):
            return x.astype("double")

        assert self._scan(f)

    def test_attribute_spelling_still_flagged(self):
        def f(x):
            return x.astype(np.float64)

        assert self._scan(f)

    def test_float32_strings_clean(self):
        def f(x):
            return x.astype("float32") + paddle.zeros([4], dtype="float32")

        assert self._scan(f) == []


# ---------------------------------------------------------------------------
# sharding readiness S201–S204
# ---------------------------------------------------------------------------

class TestShardingReadiness:
    MESH = {"data": 4, "model": 2}
    SHAPES = {"wq": (256, 128), "wo": (128, 256)}

    def _check(self, layout, shapes=None, mesh=None):
        return xray.check_sharding_readiness(
            layout, shapes or self.SHAPES, mesh or self.MESH)

    def test_valid_layout_is_clean(self):
        diags = self._check({"wq": ("data", "model"), "wo": (None, "data")})
        assert diags == []

    def test_S201_unknown_mesh_axis(self):
        diags = self._check({"wq": ("data", "expert")})
        assert _codes(diags) == ["S201"]
        assert "expert" in diags[0].message

    def test_S202_duplicate_axis_in_spec(self):
        diags = self._check({"wq": ("model", "model")})
        assert _codes(diags) == ["S202"]

    def test_S203_rank_mismatch(self):
        diags = self._check({"wq": ("data", "model", None)})
        assert _codes(diags) == ["S203"]

    def test_S204_non_divisible_dimension(self):
        diags = self._check({"wq": ("data", None)},
                            shapes={"wq": (255, 128)})
        assert _codes(diags) == ["S204"]
        assert "255" in diags[0].message

    def test_multi_axis_dim_product_divisibility(self):
        # ("data", "model") on one dim shards by 4*2=8
        diags = self._check({"wq": (("data", "model"), None)},
                            shapes={"wq": (256, 128)})
        assert diags == []
        diags = self._check({"wq": (("data", "model"), None)},
                            shapes={"wq": (252, 128)})
        assert _codes(diags) == ["S204"]

    def test_all_errors_and_sorted(self):
        diags = self._check({"wq": ("expert", "expert"),
                             "wo": ("data", "model", None)})
        assert all(d.severity == "error" for d in diags)
        # deterministic: ordered by (where, code)
        keys = [(d.where, d.code) for d in diags]
        assert keys == sorted(keys)
        assert set(_codes(diags)) == {"S201", "S202", "S203"}


# ---------------------------------------------------------------------------
# train step: trace_jaxpr donation + H108 on the undonated clone
# ---------------------------------------------------------------------------

class TestTrainStepXray:
    @pytest.fixture(scope="class")
    def fitted(self):
        from paddle_tpu.hapi import Model
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

        paddle.seed(0)
        net = LlamaForCausalLM(LlamaConfig.tiny())
        model = Model(net)
        model.prepare(
            optimizer=paddle.optimizer.AdamW(parameters=net.parameters(),
                                             learning_rate=1e-3),
            loss=paddle.nn.CrossEntropyLoss())
        ids = np.zeros((2, 16), np.int64)
        inputs = paddle.to_tensor(ids[:, :-1])
        labels = paddle.to_tensor(ids[:, 1:])
        return model, inputs, labels

    def test_model_xray_donates_state_and_is_clean(self, fitted):
        model, inputs, labels = fitted
        report = model.xray(inputs, labels, chip="cpu")
        assert report.flops > 0 and report.peak_hbm_bytes > 0
        assert any(report.donated)           # state leaves are donated
        assert report.errors() == []
        assert model.xray_report is report

    def test_H108_fires_on_undonated_clone(self, fitted):
        model, inputs, labels = fitted
        sfn = model._train_step_fn
        sfn = getattr(sfn, "_fn", sfn)
        closed, donated = sfn.trace_jaxpr([inputs], [labels])
        clean = xray.analyze_jaxpr(closed, donated=donated, chip="cpu",
                                   min_donation_bytes=1)
        undonated = xray.analyze_jaxpr(closed,
                                       donated=(False,) * len(donated),
                                       chip="cpu", min_donation_bytes=1)
        assert "H108" not in _codes(clean.hazards)
        assert "H108" in _codes(undonated.hazards)

    def test_hbm_budget_gate_raises_in_fit(self, fitted):
        model, inputs, labels = fitted
        report = model.xray(inputs, labels, chip="cpu",
                            hbm_budget_bytes=1)
        assert "H110" in _codes(report.errors())


# ---------------------------------------------------------------------------
# serving engine startup X-ray
# ---------------------------------------------------------------------------

class TestEngineXray:
    def test_engine_xray_on_start(self):
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.serving import Engine, ServingConfig

        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny())
        model.eval()
        eng = Engine(model, ServingConfig(
            max_batch_size=2, block_size=4, num_blocks=16,
            chunk_tokens=16, xray_on_start=True, xray_chip="cpu"))
        assert eng.xray_reports is not None
        names = {r.name for r in eng.xray_reports}
        assert names == {"serving::decode_step", "serving::prefill_step"}
        for r in eng.xray_reports:
            assert r.flops > 0 and r.peak_hbm_bytes > 0
            assert r.errors() == []

    @pytest.mark.parametrize("kv_cache_dtype", [None, "int8"])
    def test_an_engine_s_steps_donate_their_pool_no_H108(self,
                                                         kv_cache_dtype):
        """The decode and chunk steps return a pool of their input's
        shapes; they take it donated, so the audit finds nothing to
        double-buffer (at a threshold the tiny pool's leaves pass)."""
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.serving import Engine, ServingConfig

        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny())
        model.eval()
        cfg = ServingConfig(max_batch_size=2, block_size=4, num_blocks=16,
                            chunk_tokens=16, kv_cache_dtype=kv_cache_dtype)
        eng = Engine(model, cfg)
        leaves = sum(len(entry) for entry in eng.pool.layers)
        args = xray._serving_abstract_args(
            model, batch=2, num_blocks=16, block_size=4,
            max_blocks_per_seq=eng.max_blocks_per_seq, chunk_tokens=16,
            kv_cache_dtype=kv_cache_dtype)
        for step, step_args in zip((eng._decode_step, eng._prefill_step),
                                   args):
            report = xray.analyze(step, step_args, chip="cpu",
                                  min_donation_bytes=1)
            assert sum(report.donated) == leaves
            assert "H108" not in _codes(report.hazards)

            # the same program undonated is what H108 is for: every
            # leaf, and the ids the raw program chooses, which are the
            # shape of its ``lengths`` / ``start`` input
            def undonated(*a, _step=step):
                return _step._fn._jitted.__wrapped__(_step._fn._weights(),
                                                     *a)

            bare = xray.analyze(undonated, step_args, chip="cpu",
                                min_donation_bytes=1)
            assert _codes(bare.hazards).count("H108") == leaves + 1

    def test_engine_xray_budget_violation_raises(self):
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.serving import Engine, ServingConfig

        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny())
        model.eval()
        with pytest.raises(ValueError, match="H110"):
            Engine(model, ServingConfig(
                max_batch_size=2, block_size=4, num_blocks=16,
                chunk_tokens=16, xray_on_start=True, xray_chip="cpu",
                hbm_budget_bytes=1))


# ---------------------------------------------------------------------------
# registered-step audit (what `lint_tpu.py --xray` / CI runs)
# ---------------------------------------------------------------------------

class TestAuditDefaultSteps:
    def test_all_default_steps_clean_under_cpu_budget(self):
        reports = xray.audit_default_steps(
            chip="cpu", hbm_budget_bytes=xray.CHIPS["cpu"].hbm_bytes)
        assert len(reports) == 7
        names = {r.name for r in reports}
        assert {"moe::block_step", "ring::sp_step",
                "serving::sampled_decode_step",
                "serving::spec_verify_step"} <= names
        for r in reports:
            assert r.flops > 0
            assert r.peak_hbm_bytes < xray.CHIPS["cpu"].hbm_bytes
            assert r.errors() == []


# ---------------------------------------------------------------------------
# deterministic diagnostic / finding ordering (satellite 2)
# ---------------------------------------------------------------------------

class TestDeterministicOrder:
    def test_sort_diagnostics_by_file_line_code(self):
        D = hazards.Diagnostic
        diags = [D("H109", "error", "m", "b.py:20"),
                 D("H103", "error", "m", "b.py:3"),
                 D("H108", "warning", "m", "a.py:100"),
                 D("H103", "error", "m", "b.py:20")]
        ordered = hazards.sort_diagnostics(diags)
        assert [(d.where, d.code) for d in ordered] == [
            ("a.py:100", "H108"), ("b.py:3", "H103"),
            ("b.py:20", "H103"), ("b.py:20", "H109")]

    def test_sort_diagnostics_numeric_lines(self):
        D = hazards.Diagnostic
        diags = [D("H103", "error", "m", "f.py:10"),
                 D("H103", "error", "m", "f.py:9")]
        ordered = hazards.sort_diagnostics(diags)
        assert [d.where for d in ordered] == ["f.py:9", "f.py:10"]

    def test_lint_paths_sorted(self, tmp_path):
        pkg = tmp_path / "paddle_tpu" / "models"
        pkg.mkdir(parents=True)
        (pkg / "b.py").write_text("import jax\nimport jax.numpy\n")
        (pkg / "a.py").write_text("import jax\n")
        # paths handed in REVERSE order: output must still be sorted
        findings = astlint.lint_paths([str(pkg / "b.py"),
                                       str(pkg / "a.py")])
        keys = [(f.path, f.line, f.code) for f in findings]
        assert keys == sorted(keys)
        assert len(findings) == 3


# ---------------------------------------------------------------------------
# lint_tpu CLI exit-code contract (satellite 4)
# ---------------------------------------------------------------------------

class TestLintCliContract:
    def _run(self, *paths):
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "lint_tpu.py"),
             *paths],
            capture_output=True, text=True)

    def test_exit_zero_on_clean_tree(self, tmp_path):
        pkg = tmp_path / "paddle_tpu" / "models"
        pkg.mkdir(parents=True)
        (pkg / "ok.py").write_text("def _helper(x):\n    return x\n")
        proc = self._run(str(tmp_path))
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "0 error(s)" in proc.stdout

    def test_exit_nonzero_on_error_finding(self, tmp_path):
        pkg = tmp_path / "paddle_tpu" / "models"
        pkg.mkdir(parents=True)
        (pkg / "bad.py").write_text("import jax\n")
        proc = self._run(str(tmp_path))
        assert proc.returncode == 1
        assert "L004" in proc.stdout

    def test_suppression_restores_exit_zero(self, tmp_path):
        pkg = tmp_path / "paddle_tpu" / "models"
        pkg.mkdir(parents=True)
        (pkg / "bad.py").write_text(
            "import jax  # lint-tpu: disable=L004\n")
        proc = self._run(str(tmp_path))
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_output_order_is_stable_across_runs(self, tmp_path):
        pkg = tmp_path / "paddle_tpu" / "models"
        pkg.mkdir(parents=True)
        (pkg / "m1.py").write_text("import jax\ndef f(x=[]):\n    pass\n")
        (pkg / "m2.py").write_text("import jax\n")
        out1 = self._run(str(pkg / "m1.py"), str(pkg / "m2.py")).stdout
        out2 = self._run(str(pkg / "m2.py"), str(pkg / "m1.py")).stdout
        lines1 = [ln for ln in out1.splitlines()
                  if "L004" in ln or "L005" in ln]
        lines2 = [ln for ln in out2.splitlines()
                  if "L004" in ln or "L005" in ln]
        assert lines1 and lines1 == lines2  # CLI path order must not matter


# ---------------------------------------------------------------------------
# observability gauges
# ---------------------------------------------------------------------------

class TestXrayGauges:
    def test_export_report_gauges(self):
        from paddle_tpu import observability

        def step(a, b):
            return jnp.maximum(a @ b, 0.0)

        report = xray.analyze(step, [_sds((128, 64)), _sds((64, 32))],
                              chip="cpu", name="gauge_test_step")
        observability.enable()
        try:
            xray.export_report_gauges(report)
            text = observability.prometheus_text()
            assert "xray_static_flops" in text
            assert "xray_peak_hbm_bytes" in text
            assert "gauge_test_step" in text
        finally:
            observability.disable()
