"""Fusion-candidate miner (paddle_tpu/analysis/fusionminer) tests.

Three layers of ground truth:

1. a GOLDEN hand-computed synthetic jaxpr (matmul → add → explicit
   tanh-gelu → matmul) with exact chain boundaries, byte count and rank;
2. REDISCOVERY of both PR 13 hand-built fusions (paged gather + RoPE +
   attention; RMSNorm → matmul) as the top-ranked candidates on the
   unfused serving traces, and as F004 coverage on the fused traces —
   including the newly mined-and-built chunked-prefill kernel;
3. numerical PARITY of kernels/chunked_prefill against both its XLA
   fallback and the unfused gather-path reference.

Plus the satellite contracts: lint-tpu suppression drops a candidate
from the diagnostics AND the exit-code gate, and ranking/ordering are
deterministic with (bytes desc, file, line) tie-breaks.
"""
import importlib.util
import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp

from paddle_tpu.analysis import fusionminer as fm
from paddle_tpu.analysis.hazards import _where_key, sort_diagnostics


# ---------------------------------------------------------------------------
# golden synthetic jaxpr: matmul → add → gelu (explicit tanh form) → matmul
# ---------------------------------------------------------------------------

def _golden_fn(x, w1, w2):
    h = x @ w1
    y = h + 1.0
    t = jnp.tanh(0.7978845608 * (y + 0.044715 * y * y * y))
    z = 0.5 * y * (1.0 + t)
    return z @ w2


_M, _K, _N = 8, 16, 32


def _golden_report(**kwargs):
    f32 = jnp.float32
    closed = jax.make_jaxpr(_golden_fn)(
        jax.ShapeDtypeStruct((_M, _K), f32),
        jax.ShapeDtypeStruct((_K, _N), f32),
        jax.ShapeDtypeStruct((_N, _K), f32))
    return fm.mine_jaxpr(closed, name="golden", chip="v5e", **kwargs)


class TestGoldenChain:
    def test_exact_boundaries_bytes_and_rank(self):
        rep = _golden_report()
        assert len(rep.candidates) == 1
        assert not rep.covered
        c = rep.candidates[0]
        # chain boundaries: everything between the two weight matmuls,
        # absorbing h as dot1's epilogue and z as dot2's prologue
        assert c.code == "F001"
        assert c.rank == 1
        assert c.count == 1
        assert c.epilogue_anchors == ("dot_general",)
        assert c.prologue_anchors == ("dot_general",)
        assert c.interior_anchors == 0
        assert sorted(set(c.primitives)) == ["add", "mul", "tanh"]
        # the explicit gelu traces to exactly 10 fusible eqns: 3 adds,
        # 6 muls, 1 tanh
        assert c.n_eqns == 10
        assert sorted(c.primitives).count("mul") == 6
        # hand-computed savings, all on [8, 32] f32 intermediates
        # (1 KiB each): 9 interior vars stay in VMEM (2x each: the
        # write + the read back), h fuses as dot1's epilogue (2x), z as
        # dot2's prologue (1 write + 1 read = 2x)
        var_bytes = _M * _N * 4
        assert c.bytes_saved == (9 * 2 + 2 + 2) * var_bytes
        assert c.time_saved_s == pytest.approx(
            c.bytes_saved / fm.CHIPS["v5e"].hbm_bandwidth)

    def test_diagnostic_emitted_and_sorted(self):
        rep = _golden_report(threshold_bytes=1024.0)
        codes = [d.code for d in rep.diagnostics]
        assert codes == ["F001"]
        assert rep.diagnostics[0].severity == "warning"
        assert rep.diagnostics == sort_diagnostics(rep.diagnostics)


# ---------------------------------------------------------------------------
# deterministic ordering: equal-savings chains tie-break by (file, line)
# ---------------------------------------------------------------------------

def _twin_fn(x, w1, w2, w3, w4):
    a = jnp.tanh(x @ w1 + 1.0) @ w3
    b = jnp.tanh(x @ w2 + 2.0) @ w4
    return a + b


class TestOrderingStability:
    def _mine(self):
        f32 = jnp.float32
        closed = jax.make_jaxpr(_twin_fn)(
            jax.ShapeDtypeStruct((_M, _K), f32),
            jax.ShapeDtypeStruct((_K, _N), f32),
            jax.ShapeDtypeStruct((_K, _N), f32),
            jax.ShapeDtypeStruct((_N, _K), f32),
            jax.ShapeDtypeStruct((_N, _K), f32))
        return fm.mine_jaxpr(closed, name="twins", chip="v5e")

    def test_tiebreak_by_line(self):
        rep = self._mine()
        a, b = rep.candidates[0], rep.candidates[1]
        # both chains are {add, tanh} over [8, 32] with one epilogue and
        # one prologue matmul: identical savings, different source lines
        assert a.bytes_saved == b.bytes_saved == 6 * _M * _N * 4
        assert (a.rank, b.rank) == (1, 2)
        fa, la = _where_key(a.where)
        fb, lb = _where_key(b.where)
        assert fa == fb and la < lb

    def test_mining_twice_is_identical(self):
        one = [c.to_json() for c in self._mine().candidates]
        two = [c.to_json() for c in self._mine().candidates]
        assert one == two
        rep = self._mine()
        assert rep.diagnostics == sort_diagnostics(rep.diagnostics)


# ---------------------------------------------------------------------------
# lint-tpu suppression: a suppressed F001 drops from output AND exit gate
# ---------------------------------------------------------------------------

_SUPPRESS_SRC = """\
import jax.numpy as jnp


def chain(x, w1, w2):
    h = x @ w1
    y = jnp.tanh(h + 1.0)  {comment}
    return y @ w2
"""


def _mine_module(tmp_path, fname, comment):
    path = tmp_path / fname
    path.write_text(_SUPPRESS_SRC.format(comment=comment))
    spec = importlib.util.spec_from_file_location(
        fname[:-3], str(path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    f32 = jnp.float32
    closed = jax.make_jaxpr(mod.chain)(
        jax.ShapeDtypeStruct((_M, _K), f32),
        jax.ShapeDtypeStruct((_K, _N), f32),
        jax.ShapeDtypeStruct((_N, _K), f32))
    return fm.mine_jaxpr(closed, name=fname, chip="v5e",
                         threshold_bytes=1024.0)


class TestSuppression:
    def test_unsuppressed_f001_appears(self, tmp_path):
        rep = _mine_module(tmp_path, "plainchain.py", "")
        assert [c.code for c in rep.candidates] == ["F001"]
        assert rep.candidates[0].rank == 1
        assert not rep.candidates[0].suppressed
        assert [d.code for d in rep.diagnostics] == ["F001"]
        # the exit-code gate (--fail-on-candidates) counts this one
        assert len(rep.above_threshold()) == 1

    def test_suppressed_f001_drops(self, tmp_path):
        rep = _mine_module(
            tmp_path, "quietchain.py",
            "# lint-tpu: disable=F001 -- XLA already fuses this")
        assert len(rep.candidates) == 1
        c = rep.candidates[0]
        assert c.suppressed
        assert c.rank is None
        # dropped from the diagnostics output ...
        assert [d.code for d in rep.diagnostics] == []
        # ... and from the exit-code gate
        assert rep.above_threshold() == []
        # but still visible to tooling that asks for it (marked)
        assert c.to_json()["suppressed"] is True

    def test_suppress_false_keeps_ranking(self, tmp_path):
        rep_sup = _mine_module(
            tmp_path, "chainsup.py",
            "# lint-tpu: disable=F001 -- XLA already fuses this")
        path = str(tmp_path / "chainsup.py")
        spec = importlib.util.spec_from_file_location("chainsup2", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        f32 = jnp.float32
        closed = jax.make_jaxpr(mod.chain)(
            jax.ShapeDtypeStruct((_M, _K), f32),
            jax.ShapeDtypeStruct((_K, _N), f32),
            jax.ShapeDtypeStruct((_N, _K), f32))
        rep = fm.mine_jaxpr(closed, name="nosup", chip="v5e",
                            threshold_bytes=1024.0, suppress=False)
        assert rep_sup.candidates[0].suppressed
        assert not rep.candidates[0].suppressed
        assert rep.candidates[0].rank == 1


# ---------------------------------------------------------------------------
# rediscovery of the hand-built fusions + F004 coverage on fused traces
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def audit_reports():
    return {r.name: r for r in fm.audit_fusion(chip="v5e", fused=True)}


class TestRediscovery:
    def test_decode_attention_region_is_top_ranked(self, audit_reports):
        rep = audit_reports["serving::decode_step"]
        c = rep.candidates[0]
        # PR 13's fused_paged_decode shape: the gather + RoPE + masked
        # softmax chain SPANNING both attention matmuls, once per layer
        assert c.rank == 1
        assert c.code == "F003"
        assert c.interior_anchors == 2
        assert c.count == 2
        assert "gather" in c.primitives
        assert any(p.startswith("reduce_") for p in c.primitives)
        assert os.path.join("models", "llama.py") in c.where
        # it is the only candidate over the default CI threshold
        above = rep.above_threshold()
        assert above and above[0] is c

    def test_prefill_attention_region_is_top_ranked(self, audit_reports):
        rep = audit_reports["serving::prefill_step"]
        c = rep.candidates[0]
        assert c.rank == 1
        assert c.code == "F003"
        assert c.interior_anchors == 2
        assert c.count == 2
        assert "gather" in c.primitives
        above = rep.above_threshold()
        assert above and above[0] is c

    def test_norm_matmul_prologue_rediscovered(self, audit_reports):
        # PR 13's fused_norm_linear shape: the RMSNorm chain feeding
        # matmul prologues, once per decoder-layer norm (2 layers x 2
        # norms on the tiny audit model)
        for name in ("serving::decode_step", "serving::prefill_step"):
            rep = audit_reports[name]
            norms = [c for c in rep.candidates if c.code == "F002"]
            assert norms, f"no F002 candidate in {name}"
            c = norms[0]
            assert c.rank is not None and c.rank <= 3
            assert c.count == 4
            assert c.prologue_anchors == ("dot_general",)
            assert "rsqrt" in c.primitives
            assert os.path.join("models", "llama.py") in c.where

    def test_fused_steps_report_f004_coverage(self, audit_reports):
        decode = audit_reports["serving::decode_step[fused]"]
        prefill = audit_reports["serving::prefill_step[fused]"]
        # ... and rms_norm: the final norm, the one norm no projection
        # follows, is the Pallas rms_norm on the chip, and the forced
        # lowering now shows every kernel the chip's program holds
        # (the norm folded into the projections is XLA's own fusion since
        # PR 37, no kernel: measured on the chip, PERF.md section 6)
        assert {c.primitives[0] for c in decode.covered} == \
            {"fused_paged_decode", "rms_norm"}
        assert {c.primitives[0] for c in prefill.covered} == \
            {"fused_chunked_prefill", "rms_norm"}
        assert next(c for c in decode.covered
                    if c.primitives[0] == "rms_norm").count == 1
        # the attention kernels once per layer
        assert next(c for c in prefill.covered
                    if c.primitives[0] == "fused_chunked_prefill").count == 2
        for c in decode.covered + prefill.covered:
            assert c.code == "F004"
            assert c.rank is None

    def test_fused_steps_pass_the_ci_gate(self, audit_reports):
        # the CI stage's contract: nothing kernel-sized left unfused
        for name in ("serving::decode_step[fused]",
                     "serving::prefill_step[fused]"):
            rep = audit_reports[name]
            assert rep.above_threshold() == [], [
                (c.code, c.where, c.bytes_saved)
                for c in rep.above_threshold()]
        # F004 leaves never rank or count toward the gate
        assert all(d.code != "F004" or d.severity == "info"
                   for r in audit_reports.values() for d in r.diagnostics)

    def test_report_json_shape(self, audit_reports):
        rep = audit_reports["serving::prefill_step"]
        js = rep.to_json()
        assert js["name"] == "serving::prefill_step"
        assert js["chip"] == "v5e"
        assert js["n_above_threshold"] == len(rep.above_threshold())
        assert js["candidates"][0]["rank"] == 1
        for d in js["diagnostics"]:
            assert set(d) == {"code", "severity", "message", "where"}


# ---------------------------------------------------------------------------
# the burned-down candidate: kernels/chunked_prefill numerics
# ---------------------------------------------------------------------------

def _paged_attn_reference(q, kp, vp, bt, positions):
    """models/llama.py's unfused gather-path chunk attention."""
    B, T, H, D = q.shape
    kb = kp[bt].reshape(B, -1, kp.shape[2], kp.shape[3])
    vb = vp[bt].reshape(B, -1, vp.shape[2], vp.shape[3])
    rep = H // kb.shape[2]
    if rep > 1:
        kb = jnp.repeat(kb, rep, axis=2)
        vb = jnp.repeat(vb, rep, axis=2)
    scores = jnp.einsum("bthd,bshd->bhts", q, kb,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(D)
    pos = positions[:, None] + jnp.arange(T)
    valid = jnp.arange(kb.shape[1])[None, None, :] <= pos[:, :, None]
    scores = jnp.where(valid[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bshd->bthd", probs, vb)


def _chunk_operands(seed, B, T, H, D, KVH, bs, nbs):
    rng = np.random.default_rng(seed)
    nb = 1 + B * nbs
    q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((nb, bs, KVH, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((nb, bs, KVH, D)), jnp.float32)
    bt = jnp.asarray(1 + np.arange(B * nbs).reshape(B, nbs), jnp.int32)
    return q, kp, vp, bt


class TestChunkedPrefillKernel:
    @pytest.mark.parametrize("kvh,positions", [
        (2, [5, 0]),            # GQA rep=2; one fresh sequence
        (4, [12, 3]),           # MHA (rep=1); mid-stream chunks
    ])
    def test_parity_pallas_vs_fallback_vs_reference(self, kvh, positions):
        from paddle_tpu.kernels.chunked_prefill import \
            fused_chunked_attention

        B, T, H, D, bs, nbs = 2, 8, 4, 16, 4, 8
        q, kp, vp, bt = _chunk_operands(0, B, T, H, D, kvh, bs, nbs)
        pos = jnp.asarray(positions, jnp.int32)
        ref = _paged_attn_reference(q, kp, vp, bt, pos)
        xla = fused_chunked_attention(q, kp, vp, bt, pos,
                                      use_pallas=False)
        pallas = fused_chunked_attention(q, kp, vp, bt, pos,
                                         use_pallas=True, interpret=True)
        np.testing.assert_allclose(np.asarray(xla), np.asarray(ref),
                                   atol=1e-5, rtol=0)
        np.testing.assert_allclose(np.asarray(pallas), np.asarray(xla),
                                   atol=1e-5, rtol=0)

    def test_single_token_chunk_matches_reference(self):
        from paddle_tpu.kernels.chunked_prefill import \
            fused_chunked_attention

        B, T, H, D, KVH, bs, nbs = 2, 1, 4, 16, 2, 4, 4
        q, kp, vp, bt = _chunk_operands(1, B, T, H, D, KVH, bs, nbs)
        pos = jnp.asarray([7, 2], jnp.int32)
        ref = _paged_attn_reference(q, kp, vp, bt, pos)
        out = fused_chunked_attention(q, kp, vp, bt, pos,
                                      use_pallas=True, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=0)

    def test_force_interpret_traces_the_pallas_leaf(self):
        from paddle_tpu.kernels.chunked_prefill import (
            KERNEL_NAME, fused_chunked_attention)
        from paddle_tpu.kernels.fusion import force_pallas_interpret

        B, T, H, D, KVH, bs, nbs = 2, 8, 4, 16, 2, 4, 8
        f32 = jnp.float32
        args = (jax.ShapeDtypeStruct((B, T, H, D), f32),
                jax.ShapeDtypeStruct((1 + B * nbs, bs, KVH, D), f32),
                jax.ShapeDtypeStruct((1 + B * nbs, bs, KVH, D), f32),
                jax.ShapeDtypeStruct((B, nbs), jnp.int32),
                jax.ShapeDtypeStruct((B,), jnp.int32))
        # fresh wrappers per trace: jax's tracing cache keys on the
        # function object + avals, not on the thread-local context
        with force_pallas_interpret():
            closed = jax.make_jaxpr(
                lambda *a: fused_chunked_attention(*a))(*args)
        # the kernel sits one ``jit`` down: traced once a program
        def prims(jaxpr):
            return {e.primitive.name for e in jaxpr.eqns} | {
                name for e in jaxpr.eqns if e.primitive.name == "jit"
                for name in prims(e.params["jaxpr"].jaxpr)}

        assert "pallas_call" in prims(closed.jaxpr)
        # off the context the CPU lowering is the XLA fallback
        closed = jax.make_jaxpr(
            lambda *a: fused_chunked_attention(*a))(*args)
        assert "pallas_call" not in prims(closed.jaxpr)

    def test_kernel_cost_is_registered(self):
        from paddle_tpu.kernels.chunked_prefill import KERNEL_NAME
        from paddle_tpu.kernels.costs import lookup_kernel_cost

        fn = lookup_kernel_cost(KERNEL_NAME)
        assert fn is not None
        cost = fn([((2, 4), "int32"), ((2,), "int32"),
                   ((2, 2, 8, 16), "float32"), ((8, 4, 2, 16), "float32"),
                   ((8, 4, 2, 16), "float32")],
                  [((2, 2, 8, 16), "float32")])
        # B=2, KVH=2, RT=8, D=16, L=16: 4*B*KVH*RT*D*L MACs and the
        # through-the-table KV traffic dominate
        assert cost.flops == 4.0 * 2 * 2 * 8 * 16 * 16
        assert cost.transcendentals == 2 * 2 * 8 * 16
        assert cost.bytes_accessed > 2 * 2 * 16 * 2 * 16 * 4


# ---------------------------------------------------------------------------
# CLI surface (full audit: slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_cli_xray_fusion_json():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "lint_tpu.py"),
         "--xray", "--fusion", "--json"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    data = json.loads(proc.stdout)
    by_name = {d["name"]: d for d in data}
    fus = by_name["serving::prefill_step"]["fusion"]
    assert fus["candidates"][0]["rank"] == 1
    assert fus["candidates"][0]["code"] == "F003"
    assert fus["n_above_threshold"] >= 1
    for d in fus["diagnostics"]:
        assert set(d) == {"code", "severity", "message", "where"}
    # the xray half keeps the shardplan diagnostic shape too
    for d in by_name["serving::prefill_step"]["diagnostics"]:
        assert set(d) == {"code", "severity", "message", "where"}
