"""The gated delta-rule kernels (kernels/delta_rule.py) against the
per-position recurrence: the chunk kernel (Pallas in interpret mode, and
its XLA lowering) over chunks that end inside a sub-chunk, betas near 0
and near 2, decays near 0 and near 1, and at the served widths over a
group of heads the grid step does not fill; its blocked triangular solve
against a float64 inverse; the one-token decode kernel against
one step of the recurrence, with the rows that are not decoding left
bit-identical in both pools."""
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import delta_rule as dr

H, DK, DV = 3, 16, 32

CASES = {
    "unaligned": dict(T=150, beta=(0.0, 2.0), g=(-0.5, 0.0)),
    "beta_near_0": dict(T=96, beta=(0.0, 0.05), g=(-0.5, 0.0)),
    "beta_near_2": dict(T=96, beta=(1.9, 2.0), g=(-0.5, 0.0)),
    "decay_near_0": dict(T=96, beta=(0.0, 2.0), g=(-20.0, -5.0)),
    "decay_near_1": dict(T=96, beta=(0.0, 2.0), g=(-1e-3, 0.0)),
}


def _inputs(seed, T, beta, g, dtype=jnp.float32, heads=H, dk=DK, dv=DV):
    rng = np.random.default_rng(seed)
    q = dr.l2_normalize(jnp.asarray(rng.normal(size=(T, heads, dk)))) \
        * dk ** -0.5
    k = dr.l2_normalize(jnp.asarray(rng.normal(size=(T, heads, dk))))
    v = jnp.asarray(rng.normal(size=(T, heads, dv)), jnp.float32)
    gs = jnp.asarray(rng.uniform(*g, size=(T, heads)), jnp.float32)
    bs = jnp.asarray(rng.uniform(*beta, size=(T, heads)), jnp.float32)
    s0 = jnp.asarray(rng.normal(size=(heads, dk, dv)), jnp.float32)
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), gs, bs, s0)


def _rel(got, want):
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("pallas", [False, True], ids=["xla", "pallas"])
def test_the_chunk_kernel_is_the_recurrence(case, pallas):
    args = _inputs(1, **CASES[case])
    o_ref, s_ref = dr.delta_rule_reference(*args)
    o, s = dr.chunked_delta_rule(*args, sub_chunk=32, use_pallas=pallas,
                                 interpret=True)
    assert o.shape == o_ref.shape and s.shape == s_ref.shape
    assert _rel(o, o_ref) < 1e-4 and _rel(s, s_ref) < 1e-4


def _forward_substitution(lt):
    """``T = (I + L)^-1`` from ``lt = L^T`` one row a step, float32:
    the row-by-row solve the blocked one regroups, whose rounding is the
    yardstick."""
    c = lt.shape[-1]
    row = jnp.arange(c)[:, None]
    col = jnp.arange(c)[None, :]
    t = jnp.broadcast_to(jnp.eye(c, dtype=jnp.float32), lt.shape)
    for i in range(1, c):
        coef = jnp.sum(jnp.where(col == i, lt, 0.0), axis=-1, keepdims=True)
        upd = jnp.sum(coef * t, axis=-2, keepdims=True)
        t = jnp.where(row == i, t - upd, t)
    return t


@pytest.mark.parametrize("c", [dr.SUB_CHUNK, 40])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_blocked_solve_rounds_like_forward_substitution(case, c):
    """Each head's first sub-chunk of ``c`` (a whole number of the
    solve's blocks, or 40: blocks of 8 whose last doubling is partial):
    the blocked solve against a float64 inverse, within 4 times the
    rounding of the row-by-row solve (or of float32's epsilon)."""
    _, k, _, g, beta, _ = (np.asarray(x) for x in _inputs(1, **CASES[case]))
    k = k[:c].transpose(1, 0, 2)                         # [H, c, dk]
    G = np.cumsum(g[:c].T, axis=-1)
    i = np.arange(c)
    lower = np.where(
        i[:, None] > i[None, :],
        beta[:c].T[:, :, None] * np.einsum("hid,hjd->hij", k, k)
        * np.exp(np.minimum(G[:, :, None] - G[:, None, :], 0.0)),
        0.0).astype(np.float32)
    want = np.linalg.inv(np.eye(c) + lower.astype(np.float64))
    upper = lower.transpose(0, 2, 1)
    got = np.asarray(dr._solve_unit_lower(jnp.asarray(lower),
                                          jnp.asarray(upper)))
    by_rows = np.asarray(_forward_substitution(jnp.asarray(upper)))

    def err(t):
        return np.abs(t - want).max() / np.abs(want).max()

    assert err(got) <= 4 * max(err(by_rows), np.finfo(np.float32).eps)


@pytest.mark.parametrize("T", [150, 256])
def test_the_pallas_chunk_kernel_at_served_widths_over_a_partial_group(T):
    """Keys of 96 and values of 192, and a head count that the kernel's
    heads a grid step do not divide: the last group is padded with heads
    that compute nothing."""
    dk, dv, c = 96, 192, dr.SUB_CHUNK
    n = -(-T // c)
    heads = next(h for h in range(2, 64)
                 if h % dr._heads_per_step(h, n, c, dk, dv))
    args = _inputs(5, T, beta=(0.0, 2.0), g=(-0.5, 0.0), heads=heads,
                   dk=dk, dv=dv)
    o_ref, s_ref = dr.delta_rule_reference(*args)
    o, s = dr.chunked_delta_rule(*args, use_pallas=True, interpret=True)
    assert o.shape == o_ref.shape and s.shape == s_ref.shape
    assert _rel(o, o_ref) < 1e-4 and _rel(s, s_ref) < 1e-4


def test_the_pallas_chunk_kernel_is_its_xla_lowering_in_bf16():
    args = _inputs(2, T=128, beta=(0.0, 2.0), g=(-0.5, 0.0),
                   dtype=jnp.bfloat16)
    o_x, s_x = dr.chunked_delta_rule(*args, use_pallas=False)
    o_p, s_p = dr.chunked_delta_rule(*args, use_pallas=True, interpret=True)
    assert _rel(o_p, o_x) < 1e-3 and _rel(s_p, s_x) < 1e-3
    # bf16 products, float32 state: within a bf16 model's error
    o_ref, s_ref = dr.delta_rule_reference(*args)
    assert _rel(o_p, o_ref) < 3e-2 and _rel(s_p, s_ref) < 3e-2


def test_padding_positions_leave_the_state_as_it_was():
    q, k, v, g, b, s0 = _inputs(3, T=40, beta=(0.5, 1.5), g=(-0.3, 0.0))
    g = g.at[25:].set(0.0)
    b = b.at[25:].set(0.0)
    _, s_short = dr.delta_rule_reference(q[:25], k[:25], v[:25], g[:25],
                                         b[:25], s0)
    for pallas in (False, True):
        _, s = dr.chunked_delta_rule(q, k, v, g, b, s0, sub_chunk=16,
                                     use_pallas=pallas, interpret=True)
        assert _rel(s, s_short) < 1e-4


@pytest.mark.parametrize("pallas", [False, True], ids=["xla", "pallas"])
def test_the_decode_kernel_is_one_step_and_leaves_idle_rows_alone(pallas):
    rng = np.random.default_rng(4)
    B, N, W = 5, 7, 2 * DK + DV
    x = jnp.asarray(rng.normal(size=(B, H, W)), jnp.float32)
    taps = jnp.asarray(rng.normal(size=(4, H, W)) * 0.5, jnp.float32)
    g = jnp.asarray(rng.uniform(-0.5, 0.0, size=(B, H)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.0, 2.0, size=(B, H)), jnp.float32)
    states = jnp.asarray(rng.normal(size=(N, H, DK, DV)), jnp.float32)
    convs = jnp.asarray(rng.normal(size=(N, 3, H, W)), jnp.float32)
    slots = jnp.asarray([3, 1, 5, 2, 6], jnp.int32)
    active = jnp.asarray([True, False, True, True, False])
    keep_s, keep_c = np.array(states), np.array(convs)
    o, s_pool, c_pool = dr.delta_rule_decode(
        x[..., :DK], x[..., DK:2 * DK], x[..., 2 * DK:], g, beta, taps,
        states, convs, slots, active, use_pallas=pallas, interpret=True)
    for row in range(B):
        slot = int(slots[row])
        if not active[row]:
            # a row between two chunks of its prompt: its slot untouched
            np.testing.assert_array_equal(np.asarray(s_pool[slot]),
                                          keep_s[slot])
            np.testing.assert_array_equal(np.asarray(c_pool[slot]),
                                          keep_c[slot])
            continue
        y = dr.short_conv(convs[slot], x[row], taps)
        q = dr.l2_normalize(y[:, :DK]) * DK ** -0.5
        k = dr.l2_normalize(y[:, DK:2 * DK])
        o1, s1 = dr.delta_rule_reference(q[None], k[None], y[None, :, 2 * DK:],
                                         g[row][None], beta[row][None],
                                         states[slot])
        assert _rel(o[row], o1[0]) < 1e-5 and _rel(s_pool[slot], s1) < 1e-5
        np.testing.assert_array_equal(
            np.asarray(c_pool[slot]),
            np.concatenate([keep_c[slot][1:], np.asarray(x[row])[None]]))
    # a slot no row names is untouched (idle rows are pointed at the
    # garbage slot 0)
    np.testing.assert_array_equal(np.asarray(s_pool[4]), keep_s[4])
    np.testing.assert_array_equal(np.asarray(c_pool[4]), keep_c[4])
