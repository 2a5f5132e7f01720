"""Gated delta-rule kernels (Pallas) with XLA fallbacks: the recurrent
layers of a hybrid decoder (Gated DeltaNet, Yang et al., arXiv:2412.06464,
in its ``fla`` form; negative eigenvalues, Grazzi et al.,
arXiv:2411.12537).

For a head at position ``t``, with ``q_t, k_t`` L2-normalised (``q_t``
also scaled by ``dk^-1/2``), ``g_t <= 0`` a log decay (``alpha_t =
exp(g_t)``) and ``beta_t`` in ``(0, 2)``::

    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
    o_t = S_t^T q_t

with ``S`` a ``[dk, dv]`` float32 state a head.  A sequence's state is
the whole of what such a layer keeps of its past: the serving pool holds
one a sequence (``serving/cache.py``, a ``state`` record), not one a
position.

``chunked_delta_rule`` runs a prompt chunk from a given state and gives
the state at its end.  It works in SUB-CHUNKS of ``sub_chunk`` positions
(the WY/UT form of arXiv:2406.06484): with ``G`` the cumulative log
decay inside a sub-chunk, ``Gamma_i = exp(G_i)`` and only ``exp(G_i -
G_j)`` for ``i >= j`` ever formed (never a positive exponent),

    L[i, j] = beta_i (k_i . k_j) exp(G_i - G_j)       (i > j)
    T       = (I + L)^-1                               (unit lower triangular)
    U       = T (beta V) - T (beta Gamma K) S          (the sub-chunk's deltas)
    O       = (Gamma Q) S + ((Q K^T) * exp(G_i - G_j))_{i >= j} U
    S'      = Gamma_c S + (K * exp(G_c - G))^T U

The triangular system is solved in float32 blockwise: its diagonal
blocks by forward substitution on the vector unit, a row of every block
of every head of a grid step at once, then the blocks doubled by
products on the MXU (``_solve_unit_lower``).  It is forward
substitution's arithmetic grouped differently: with ``beta`` up to 2 the
rows of ``T`` are what the recurrence makes them, and no power of ``L``
is formed.  The Pallas kernel takes several heads a grid step
(``_heads_per_step``, from the shapes and a VMEM budget), so that their
independent chains overlap.  Products are in the operands' type with float32
accumulation (float32 operands at full precision, the MXU's multi-pass
product); the state is float32 throughout.  A position with ``g = 0``
and ``beta = 0`` leaves the state as it was (the padding of a prompt's
last chunk).

``delta_rule_decode`` is the one-token step of a bucket of rows: each
row's causal short convolution from its conv window, SiLU, the L2 norms,
and the state update, on the DONATED state and conv pools, read and
written in place at the row's slot.  A row that is not decoding
(``active`` false) is pointed at the pool's garbage slot 0 and computes
nothing: no live slot is read or written for it.

Numerics contract: ``_xla_chunked`` and ``_xla_decode`` are the same
math in plain XLA (the chunk's triangular solve by
``lax.linalg.triangular_solve``); off the TPU the kernels lower through
them.  ``delta_rule_reference`` is the per-position recurrence, the
parity oracle.

Layout of a row's convolution channels, a head at a time: ``[q (dk) | k
(dk) | v (dv)]`` (``W = 2 dk + dv`` lanes a head), so that one head's
three inputs lie in one row of the conv window and of its taps.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK_KERNEL_NAME = "chunked_delta_rule"
DECODE_KERNEL_NAME = "delta_rule_decode"
SUB_CHUNK = 64
# ``fla``'s l2norm: x / sqrt(sum(x^2) + eps)
L2_EPS = 1e-6
_NEG = -1e30
_VMEM_LIMIT = 64 * 1024 * 1024
# the chunk kernel: VMEM a grid step's heads may fill, and the diagonal
# blocks its triangular solve substitutes before doubling them by products
_GROUP_VMEM = 32 * 1024 * 1024
_SOLVE_BLOCK = 16


def l2_normalize(x):
    """``x / sqrt(sum(x^2) + L2_EPS)`` over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                             + L2_EPS)


def short_conv(window, x, taps):
    """The causal depthwise convolution's output for ``x`` given the
    ``K - 1`` inputs before it: ``sum_j taps[j] * [window, x][j]``, tap
    ``K - 1`` on ``x``, then SiLU, float32.  ``window [..., K-1, *C]``,
    ``x [..., *C]``, ``taps [K, *C]``, ``C`` two axes (heads, lanes)."""
    k = taps.shape[0]
    y = taps[k - 1] * x.astype(jnp.float32)
    for j in range(k - 1):
        y = y + taps[j] * window[..., j, :, :].astype(jnp.float32)
    return jax.nn.silu(y)


# ---------------------------------------------------------------------------
# the per-position recurrence: the parity oracle
# ---------------------------------------------------------------------------

def delta_rule_reference(q, k, v, g, beta, state0):
    """The recurrence of the module docstring, one position at a time,
    in float32: ``q, k [T, H, dk]``, ``v [T, H, dv]``, ``g, beta [T,
    H]``, ``state0 [H, dk, dv]`` -> ``(o [T, H, dv], state [H, dk,
    dv])``."""
    f32 = jnp.float32

    def step(s, inputs):
        qt, kt, vt, gt, bt = inputs
        s = jnp.exp(gt)[:, None, None] * s
        r = jnp.einsum("hk,hkv->hv", kt, s)
        s = s + bt[:, None, None] * kt[:, :, None] * (vt - r)[:, None, :]
        return s, jnp.einsum("hk,hkv->hv", qt, s)

    with jax.default_matmul_precision("highest"):
        state, o = jax.lax.scan(
            step, state0.astype(f32),
            (q.astype(f32), k.astype(f32), v.astype(f32), g.astype(f32),
             beta.astype(f32)))
    return o, state


# ---------------------------------------------------------------------------
# chunk: Pallas
# ---------------------------------------------------------------------------

def _heads_per_step(H, n, c, dk, dv):
    """Heads a grid step of the chunk kernel: as many as fit
    ``_GROUP_VMEM`` bytes (their blocks, in lane-padded float32 and
    double-buffered, and the step's working set), spread evenly over the
    steps that takes; the last step is padded up to a whole group."""
    def lanes(x):
        return -(-x // 128) * 128

    blocks = n * c * (2 * lanes(dk) + 2 * lanes(dv) + 2 * 128) \
        + n * (dk + 16) * lanes(c) + 2 * dk * lanes(dv)
    work = n * c * (6 * lanes(c) + lanes(dk) + 2 * lanes(dv))
    most = max(1, min(H, _GROUP_VMEM // (4 * (2 * blocks + work))))
    return -(-H // -(-H // most))


def _dot(a, b):
    """``[B, m, k] x [B, k, n]`` with float32 accumulation: float32
    operands at full precision (the MXU's multi-pass product), narrower
    ones exact in one pass, whatever precision a caller asks of float32
    products."""
    precision = jax.lax.Precision.HIGHEST if a.dtype == jnp.float32 \
        else jax.lax.Precision.DEFAULT
    return jax.lax.dot_general(
        a, b, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32, precision=precision)


def _solve_unit_lower(l, lt):
    """``T = (I + L)^-1`` of each ``[c, c]`` matrix of a batch, ``L``
    strictly lower and ``lt = L^T``, float32.  The diagonal blocks of
    ``b`` (``_SOLVE_BLOCK``, or what of it divides ``c``) by forward
    substitution, a row of every block of every matrix a step (``b - 1``
    steps; the coefficients ``L[r, j]`` of the block's rows ``j`` are a
    lane-masked reduce of ``lt``, their update a reduce over the block's
    sublanes); then the blocks doubled by products until they span the
    matrix: with ``T_m`` the inverse of ``I + L``'s diagonal blocks of
    ``m`` and ``F`` the lower-left ``m``-block of each ``2m``-block of
    ``L``, ``T_2m = T_m - T_m (F T_m)``.  The same sums as forward
    substitution, grouped; no power of ``L`` is formed."""
    nb, c, _ = l.shape
    b = math.gcd(c, _SOLVE_BLOCK)
    row = jax.lax.broadcasted_iota(jnp.int32, l.shape, 1)
    col = jax.lax.broadcasted_iota(jnp.int32, l.shape, 2)
    first = row // b * b                    # the first row of row's block
    t = jnp.where(row == col, 1.0, 0.0).astype(jnp.float32)
    for s in range(1, b):
        # each row j's coefficient in the row ``first(j) + s`` of its block
        coef = jnp.sum(jnp.where(col == first + s, lt, 0.0), axis=-1,
                       keepdims=True)
        upd = jnp.sum((coef * t).reshape(nb, c // b, b, c), axis=2,
                      keepdims=True)
        upd = jnp.broadcast_to(upd, (nb, c // b, b, c)).reshape(nb, c, c)
        t = jnp.where(row == first + s, t - upd, t)
    m = b
    while m < c:
        below = (row // m == col // m + 1) & (row // m % 2 == 1)
        t = t - _dot(t, _dot(jnp.where(below, l, 0.0), t))
        m *= 2
    return t


def _chunk_kernel(q_ref, k_ref, kt_ref, v_ref, gc_ref, gr_ref, bc_ref,
                  br_ref, s0_ref, o_ref, s1_ref):
    """A group of heads: every sub-chunk's triangular system and
    state-independent products at once, then the ``n`` sub-chunks in
    order on the group's states.  Products that share an operand are one
    product of the others stacked by rows (``[K; Q] K^T``, ``[W; Gamma Q]
    S``, ``[within; K decay^T] U``); in the operands' type (``mm``),
    float32 accumulation.  Blocks are sub-chunk major, ``[n, G, ...]``."""
    mm, f32 = q_ref.dtype, jnp.float32
    n, g, c, _ = q_ref.shape
    nb = n * g

    def whole(ref):
        return ref[...].reshape((nb,) + ref.shape[2:])

    q, k, kt = whole(q_ref), whole(k_ref), whole(kt_ref)
    gc, gr, bc, br = whole(gc_ref), whole(gr_ref), whole(bc_ref), \
        whole(br_ref)
    row = jax.lax.broadcasted_iota(jnp.int32, (nb, c, c), 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (nb, c, c), 2)
    kq = _dot(jnp.concatenate([k, q], axis=1), kt)
    kk, qk = kq[:, :c], kq[:, c:]           # K K^T is symmetric: L and L^T
    # L[i, j] = beta_i (k_i . k_j) exp(G_i - G_j), i > j
    lower = bc * kk * jnp.exp(jnp.where(row > col, gc - gr, _NEG))
    upper = br * kk * jnp.exp(jnp.where(col > row, gr - gc, _NEG))
    t = _solve_unit_lower(lower, upper).astype(mm)
    gamma = jnp.exp(gc)                                  # [nb, c, 1]
    w = _dot(t, (k.astype(f32) * (bc * gamma)).astype(mm))
    ut = _dot(t, (whole(v_ref).astype(f32) * bc).astype(mm))
    within = qk * jnp.exp(jnp.where(row >= col, gc - gr, _NEG))
    # the sub-chunk's last (and least: G falls) cumulative decay
    g_end = jnp.min(gc, axis=1, keepdims=True)           # [nb, 1, 1]
    kd = kt.astype(f32) * jnp.exp(g_end - gr)            # [nb, dk, c]
    on_state = jnp.concatenate([w, q.astype(f32) * gamma], axis=1)
    on_delta = jnp.concatenate([within, kd], axis=1)

    def at(x, s):
        return x.reshape((n, g) + x.shape[1:])[s]

    state = s0_ref[...]                                  # [G, dk, dv]
    for s in range(n):
        x = _dot(at(on_state, s).astype(mm), state.astype(mm))
        u = at(ut, s) - x[:, :c]
        y = _dot(at(on_delta, s).astype(mm), u.astype(mm))
        o_ref[s] = x[:, c:] + y[:, :c]
        state = jnp.exp(at(g_end, s)) * state + y[:, c:]
    s1_ref[...] = state


def _layouts(q, k, v, g, beta, c):
    """The head-major, sub-chunked operands: ``q, k [H, n, c, dk]`` (their
    own type), ``k^T [H, n, dk, c]``, ``v [H, n, c, dv]``, the
    cumulative log decay and ``beta`` as columns ``[H, n, c, 1]`` and
    rows ``[H, n, 1, c]``, float32."""
    T, H, dk = q.shape
    n = T // c

    def heads(x):
        return x.transpose(1, 0, 2).reshape(H, n, c, x.shape[-1])

    qh, kh, vh = heads(q), heads(k), heads(v).astype(q.dtype)
    G = jnp.cumsum(g.astype(jnp.float32).T.reshape(H, n, c), axis=-1)
    b = beta.astype(jnp.float32).T.reshape(H, n, c)
    return (qh, kh, kh.transpose(0, 1, 3, 2), vh, G[..., None],
            G[..., None, :], b[..., None], b[..., None, :])


@functools.partial(jax.jit, static_argnames=("c", "interpret"))
def _pallas_chunked(q, k, v, g, beta, state0, c, interpret):
    T, H, dk = q.shape
    dv = v.shape[-1]
    n = T // c
    group = _heads_per_step(H, n, c, dk, dv)
    pad = -H % group
    # sub-chunk major, the heads padded to whole groups with heads that
    # compute nothing (no input, no decay, a zero state)
    ops = [jnp.pad(x.swapaxes(0, 1), [(0, 0), (0, pad), (0, 0), (0, 0)])
           for x in _layouts(q, k, v, g, beta, c)]
    s0 = jnp.pad(state0.astype(jnp.float32), [(0, pad), (0, 0), (0, 0)])

    def spec(*tail):
        return pl.BlockSpec((n, group) + tail,
                            lambda h: (0, h) + (0,) * len(tail))

    state_spec = pl.BlockSpec((group, dk, dv), lambda h: (h, 0, 0))
    o, state1 = pl.pallas_call(
        _chunk_kernel,
        grid=((H + pad) // group,),
        in_specs=[spec(c, dk), spec(c, dk), spec(dk, c), spec(c, dv),
                  spec(c, 1), spec(1, c), spec(c, 1), spec(1, c),
                  state_spec],
        out_specs=[spec(c, dv), state_spec],
        out_shape=[jax.ShapeDtypeStruct((n, H + pad, c, dv), jnp.float32),
                   jax.ShapeDtypeStruct((H + pad, dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT) if not interpret else None,
        cost_estimate=pl.CostEstimate(
            flops=H * n * (6 * c * dk * dv + c * c * (6 * dk + 4 * dv)),
            bytes_accessed=H * (n * c * (3 * dk + dv) * 2 + n * c * dv * 4
                                + 2 * dk * dv * 4),
            transcendentals=H * n * c * (2 * c + 2)),
        interpret=interpret,
        name=CHUNK_KERNEL_NAME,
    )(*ops, s0)
    o = o[:, :H].transpose(0, 2, 1, 3).reshape(T, H, dv)
    return o, state1[:H]


# ---------------------------------------------------------------------------
# chunk: the same math in XLA
# ---------------------------------------------------------------------------

def _xla_chunked(q, k, v, g, beta, state0, c):
    mm, f32 = q.dtype, jnp.float32
    T, H, dk = q.shape
    dv = v.shape[-1]
    n = T // c
    qh, kh, _, vh, gc, gr, bc, br = _layouts(q, k, v, g, beta, c)
    row = jnp.arange(c)[:, None]
    col = jnp.arange(c)[None, :]
    kk = jnp.einsum("hncd,hnmd->hncm", kh, kh, preferred_element_type=f32)
    lower = bc * kk * jnp.exp(jnp.where(row > col, gc - gr, _NEG))
    eye = jnp.broadcast_to(jnp.eye(c, dtype=f32), lower.shape)
    t_all = jax.lax.linalg.triangular_solve(
        eye + lower, eye, left_side=True, lower=True,
        unit_diagonal=True).astype(mm)
    gamma = jnp.exp(gc)
    bv = (vh.astype(f32) * bc).astype(mm)
    bk = (kh.astype(f32) * (bc * gamma)).astype(mm)
    w = jnp.einsum("hncm,hnmd->hncd", t_all, bk, preferred_element_type=f32)
    ut = jnp.einsum("hncm,hnmd->hncd", t_all, bv, preferred_element_type=f32)
    qk = jnp.einsum("hncd,hnmd->hncm", qh, kh, preferred_element_type=f32)
    within = (qk * jnp.exp(jnp.where(row >= col, gc - gr, _NEG))).astype(mm)
    qg = (qh.astype(f32) * gamma).astype(mm)
    g_end = gc[:, :, c - 1:, :]                          # [H, n, 1, 1]
    kd = (kh.astype(f32) * jnp.exp(g_end - gc)).astype(mm)
    state, outs = state0.astype(f32), []
    for s in range(n):
        sb = state.astype(mm)
        u = ut[:, s] - jnp.einsum("hcd,hde->hce", w[:, s].astype(mm), sb,
                                  preferred_element_type=f32)
        ub = u.astype(mm)
        outs.append(
            jnp.einsum("hcd,hde->hce", qg[:, s], sb,
                       preferred_element_type=f32)
            + jnp.einsum("hcm,hme->hce", within[:, s], ub,
                         preferred_element_type=f32))
        state = jnp.exp(g_end[:, s]) * state + jnp.einsum(
            "hcd,hce->hde", kd[:, s], ub, preferred_element_type=f32)
    o = jnp.stack(outs, axis=1).reshape(H, T, dv)
    return o.transpose(1, 0, 2), state


def chunked_delta_rule(q, k, v, g, beta, state0, *, sub_chunk=SUB_CHUNK,
                       use_pallas=None, interpret=None):
    """A chunk of one sequence from ``state0``: ``q, k [T, H, dk]``
    (normalised, q scaled; their type is the products'), ``v [T, H,
    dv]``, ``g [T, H]`` log decays (<= 0), ``beta [T, H]``, ``state0 [H,
    dk, dv]`` float32.  Returns
    ``(o [T, H, dv] float32, state [H, dk, dv] float32)``, the state
    after the chunk's last position.  A ``T`` that is not a whole number
    of sub-chunks is padded with positions that leave the state as it
    was."""
    from .fusion import pallas_lowering

    T = q.shape[0]
    # a short chunk is one sub-chunk of whole sublane tiles
    c = min(sub_chunk, -(-T // 8) * 8)
    pad = -T % c
    if pad:
        def padded(x):
            return jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))

        q, k, v, g, beta = (padded(x) for x in (q, k, v, g, beta))
    use_pallas, interpret = pallas_lowering(use_pallas, interpret)
    if use_pallas:
        o, state = _pallas_chunked(q, k, v, g, beta, state0, c, interpret)
    else:
        o, state = _xla_chunked(q, k, v, g, beta, state0, c)
    return o[:T], state


# ---------------------------------------------------------------------------
# decode: Pallas
# ---------------------------------------------------------------------------

def _decode_kernel(slots_ref, active_ref, alpha_ref, beta_ref, x_ref, w_ref,
                   s_ref, c_ref, o_ref, s_out, c_out, *, dk, taps):
    """One row: its conv window and state at its slot (BlockSpecs of
    the scalar-prefetched slots), nothing where the row is idle; the
    row's decays and betas are scalars a head."""
    del slots_ref
    f32 = jnp.float32
    b = pl.program_id(0)

    @pl.when(active_ref[b] != 0)
    def _():
        x = x_ref[0]                                     # [H, W]
        win = c_ref[0]                                   # [K-1, H, W]
        y = w_ref[taps - 1] * x.astype(f32)
        for j in range(taps - 1):
            y = y + w_ref[j] * win[j].astype(f32)
        y = y * jax.nn.sigmoid(y)
        for j in range(taps - 2):
            c_out[0, j] = win[j + 1]
        c_out[0, taps - 2] = x.astype(c_out.dtype)
        heads = y.shape[0]
        q = y[:, :dk]
        k = y[:, dk:2 * dk]
        v = y[:, 2 * dk:]
        q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True)
                              + L2_EPS) * (dk ** -0.5)
        k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True)
                              + L2_EPS)
        qt, kt = q.T, k.T                                # [dk, H]
        outs = []
        for h in range(heads):
            s = s_ref[0, h] * alpha_ref[b, h]            # [dk, dv]
            kc = kt[:, h:h + 1]
            r = jnp.sum(kc * s, axis=0, keepdims=True)   # [1, dv]
            s = s + kc * ((v[h:h + 1, :] - r) * beta_ref[b, h])
            s_out[0, h] = s
            outs.append(jnp.sum(qt[:, h:h + 1] * s, axis=0, keepdims=True))
        o_ref[0] = jnp.concatenate(outs, axis=0)


@functools.partial(jax.jit, static_argnames=("dk", "interpret"))
def _pallas_decode(x, taps, g, beta, state_pool, conv_pool, slots, active,
                   dk, interpret):
    B, H, W = x.shape
    K = taps.shape[0]
    dv = state_pool.shape[-1]

    def row(*tail):
        return pl.BlockSpec((1,) + tail,
                            lambda b, *_: (b,) + (0,) * len(tail))

    def at_slot(*tail):
        return pl.BlockSpec((1,) + tail,
                            lambda b, s, *_: (s[b],) + (0,) * len(tail))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=[row(H, W),
                  pl.BlockSpec((K, H, W), lambda b, *_: (0, 0, 0)),
                  at_slot(H, dk, dv), at_slot(K - 1, H, W)],
        out_specs=[row(H, dv), at_slot(H, dk, dv), at_slot(K - 1, H, W)])
    return pl.pallas_call(
        functools.partial(_decode_kernel, dk=dk, taps=K),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state_pool.shape, state_pool.dtype),
                   jax.ShapeDtypeStruct(conv_pool.shape, conv_pool.dtype)],
        # operands: slots, active, alpha, beta, x, taps, state, conv
        input_output_aliases={6: 1, 7: 2},
        # rows run in order: a row's slot is written back before a
        # later row could be given the same block
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT) if not interpret else None,
        cost_estimate=pl.CostEstimate(
            flops=B * H * 8 * dk * dv,
            bytes_accessed=B * (2 * H * dk * dv * 4
                                + 2 * (K - 1) * H * W
                                * conv_pool.dtype.itemsize),
            transcendentals=B * H * W),
        interpret=interpret,
        name=DECODE_KERNEL_NAME,
    )(slots, active, jnp.exp(g), beta, x, taps, state_pool, conv_pool)


def _xla_decode(x, taps, g, beta, state_pool, conv_pool, slots, active, dk):
    """The decode kernel's math in XLA: gather at the slots, update,
    scatter back where ``active`` (an idle row's write is dropped)."""
    f32 = jnp.float32
    win = conv_pool[slots]                               # [B, K-1, H, W]
    y = short_conv(win, x, taps)                         # [B, H, W]
    q = l2_normalize(y[..., :dk]) * (dk ** -0.5)
    k = l2_normalize(y[..., dk:2 * dk])
    v = y[..., 2 * dk:]
    s = jnp.exp(g)[..., None, None] * state_pool[slots].astype(f32)
    r = jnp.einsum("bhk,bhkv->bhv", k, s, precision="highest")
    s = s + k[..., None] * (beta[..., None] * (v - r))[:, :, None, :]
    o = jnp.einsum("bhk,bhkv->bhv", q, s, precision="highest")
    write = jnp.where(active, slots, state_pool.shape[0])
    new_win = jnp.concatenate([win[:, 1:], x[:, None].astype(win.dtype)],
                              axis=1)
    return (o, state_pool.at[write].set(s, mode="drop"),
            conv_pool.at[write].set(new_win, mode="drop"))


def delta_rule_decode(q, k, v, g, beta, conv_w, state_pool, conv_pool,
                      slots, active, *, use_pallas=None, interpret=None):
    """One token a row: ``q, k [B, H, dk]`` and ``v [B, H, dv]`` are the
    new token's projections BEFORE the short convolution; ``g, beta [B,
    H]`` float32; ``conv_w [K, H, 2 dk + dv]`` the convolution's taps in
    the per-head channel layout (module docstring), tap ``K - 1`` on the
    new token; ``state_pool [N, H, dk, dv]`` float32 and ``conv_pool [N,
    K - 1, H, 2 dk + dv]`` the layer's pools (DONATED: the returned
    pools are the same buffers); ``slots [B]`` int32 each row's slot,
    ``active [B]`` bool.  Returns ``(o [B, H, dv] float32, state_pool,
    conv_pool)``.  A row with ``active`` false reads and writes no live
    slot, and its ``o`` is not defined."""
    from .fusion import pallas_lowering

    dk = q.shape[-1]
    x = jnp.concatenate([q, k, v], axis=-1)
    slots = jnp.where(active, jnp.asarray(slots, jnp.int32), 0)
    taps = conv_w.astype(jnp.float32)
    g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
    use_pallas, interpret = pallas_lowering(use_pallas, interpret)
    if use_pallas:
        return _pallas_decode(x, taps, g, beta, state_pool, conv_pool,
                              slots, active.astype(jnp.int32), dk,
                              interpret)
    return _xla_decode(x, taps, g, beta, state_pool, conv_pool, slots,
                       active, dk)
