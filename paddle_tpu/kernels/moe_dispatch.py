"""MoE token dispatch/combine Pallas kernels.

TPU-native replacement for the reference MoE routing collectives+kernels
(/root/reference/paddle/fluid/operators/collective/global_scatter_op.* and
incubate moe_layer's dispatch): GShard-style capacity-padded routing
expressed as one-hot matmuls, with the [T, E*C] one-hot built ON THE FLY
in VMEM from the (expert, slot) index pairs — the XLA einsum formulation
must materialize that one-hot in HBM (T*E*C floats, often larger than the
activations themselves).

dispatch:  tokens [T, M] → [E, C, M]   (weights optional)
combine :  expert_out [E, C, M], gates → [T, M]
Both are custom-vjp pairs of each other, so grads stay kernel-fused.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BT = 256
DEFAULT_BC = 128


def moe_capacity(tokens: int, experts: int, top_k: int,
                 capacity_factor: float = 1.0) -> int:
    """GShard expert capacity: ceil(capacity_factor * T * K / E), the C
    in the padded [E, C, M] dispatch buffer."""
    return max(1, -(-int(tokens * top_k * capacity_factor) // experts))


def _resolve_interpret(interpret):
    """None → real kernel on TPU, XLA one-hot einsum fallback elsewhere
    (keeps CPU traces analyzable: the static analyzers and tier-1 see
    plain einsums instead of an opaque interpreted pallas_call).
    Explicit True still forces pallas interpret mode (kernel-logic
    parity testing); explicit False demands the real kernel."""
    if interpret is None:
        return False if jax.default_backend() == "tpu" else "xla"
    return interpret


def _dispatch_kernel(tok_ref, eidx_ref, sidx_ref, w_ref, o_ref, acc_ref, *,
                     expert_block_c0, K, bc):
    e = pl.program_id(0)
    ci = pl.program_id(1)
    ti = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(ti == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    tok = tok_ref[:].astype(jnp.float32)          # [bt, M]
    bt = tok.shape[0]
    c0 = ci * bc
    slots = jax.lax.broadcasted_iota(jnp.int32, (bt, bc), 1) + c0
    p = jnp.zeros((bt, bc), jnp.float32)
    for k in range(K):  # K is tiny (top-1/top-2)
        ek = eidx_ref[:, k][:, None]
        sk = sidx_ref[:, k][:, None]
        wk = w_ref[:, k][:, None].astype(jnp.float32)
        p = p + jnp.where((ek == e) & (sk == slots), wk, 0.0)
    acc_ref[:] += jax.lax.dot_general(
        p, tok, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ti == nt - 1)
    def _finalize():
        o_ref[0] = acc_ref[:].astype(o_ref.dtype)


def _combine_kernel(eo_ref, eidx_ref, sidx_ref, w_ref, o_ref, acc_ref, *,
                    C, K, bj):
    ti = pl.program_id(0)
    ji = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(ji == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    eo = eo_ref[:].astype(jnp.float32)  # [bj, M] slice of [E*C, M]
    bt = eidx_ref.shape[0]
    j0 = ji * bj
    cols = jax.lax.broadcasted_iota(jnp.int32, (bt, bj), 1) + j0
    p = jnp.zeros((bt, bj), jnp.float32)
    for k in range(K):
        flat = (eidx_ref[:, k] * C + sidx_ref[:, k])[:, None]
        wk = w_ref[:, k][:, None].astype(jnp.float32)
        p = p + jnp.where(flat == cols, wk, 0.0)
    acc_ref[:] += jax.lax.dot_general(
        p, eo, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ji == nj - 1)
    def _finalize():
        o_ref[:] = acc_ref[:].astype(o_ref.dtype)


def _dispatch_raw(tokens, eidx, sidx, weights, E, C, bt, bc, interpret):
    if interpret == "xla":
        return _dispatch_xla(tokens, eidx, sidx, weights, E, C)
    T, M = tokens.shape
    K = eidx.shape[1]
    bt_ = min(bt, T)
    bc_ = min(bc, C)
    if T % bt_ or C % bc_:
        return _dispatch_xla(tokens, eidx, sidx, weights, E, C)
    out = pl.pallas_call(
        functools.partial(_dispatch_kernel, expert_block_c0=0, K=K, bc=bc_),
        grid=(E, C // bc_, T // bt_),
        in_specs=[
            pl.BlockSpec((bt_, M), lambda e, c, t: (t, 0)),
            pl.BlockSpec((bt_, K), lambda e, c, t: (t, 0)),
            pl.BlockSpec((bt_, K), lambda e, c, t: (t, 0)),
            pl.BlockSpec((bt_, K), lambda e, c, t: (t, 0)),
        ],
        out_specs=pl.BlockSpec((1, bc_, M), lambda e, c, t: (e, c, 0)),
        out_shape=jax.ShapeDtypeStruct((E, C, M), tokens.dtype),
        scratch_shapes=[pltpu.VMEM((bc_, M), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
        if not interpret else None,
    )(tokens, eidx, sidx, weights)
    return out


def _combine_raw(expert_out, eidx, sidx, weights, bt, bj, interpret):
    if interpret == "xla":
        return _combine_xla(expert_out, eidx, sidx, weights)
    E, C, M = expert_out.shape
    T, K = eidx.shape
    bt_ = min(bt, T)
    bj_ = min(bj, E * C)
    if T % bt_ or (E * C) % bj_:
        return _combine_xla(expert_out, eidx, sidx, weights)
    eo = expert_out.reshape(E * C, M)
    out = pl.pallas_call(
        functools.partial(_combine_kernel, C=C, K=K, bj=bj_),
        grid=(T // bt_, (E * C) // bj_),
        in_specs=[
            pl.BlockSpec((bj_, M), lambda t, j: (j, 0)),
            pl.BlockSpec((bt_, K), lambda t, j: (t, 0)),
            pl.BlockSpec((bt_, K), lambda t, j: (t, 0)),
            pl.BlockSpec((bt_, K), lambda t, j: (t, 0)),
        ],
        out_specs=pl.BlockSpec((bt_, M), lambda t, j: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((T, M), expert_out.dtype),
        scratch_shapes=[pltpu.VMEM((bt_, M), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))
        if not interpret else None,
    )(eo, eidx, sidx, weights)
    return out


def _dispatch_xla(tokens, eidx, sidx, weights, E, C):
    onehot = (jax.nn.one_hot(eidx, E, dtype=tokens.dtype)[..., None]
              * jax.nn.one_hot(sidx, C, dtype=tokens.dtype)[..., None, :])
    onehot = (onehot * weights[..., None, None].astype(tokens.dtype)).sum(1)
    return jnp.einsum("tec,tm->ecm", onehot, tokens)


def _combine_xla(expert_out, eidx, sidx, weights):
    gathered = expert_out[eidx, sidx]  # [T, K, M]
    return (gathered * weights[..., None].astype(expert_out.dtype)).sum(1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def moe_dispatch(tokens, eidx, sidx, weights, E, C, bt=DEFAULT_BT,
                 bc=DEFAULT_BC, interpret=None):
    """Route tokens to [E, C, M] expert buffers.

    eidx/sidx: [T, K] int32 expert id and capacity slot per choice (use
    slot >= C to drop a choice); weights: [T, K] scale per choice (1.0 for
    plain dispatch)."""
    interpret = _resolve_interpret(interpret)
    return _dispatch_raw(tokens, eidx, sidx, weights, E, C, bt, bc,
                         interpret)


def _moe_dispatch_fwd(tokens, eidx, sidx, weights, E, C, bt, bc, interpret):
    interpret = _resolve_interpret(interpret)
    out = _dispatch_raw(tokens, eidx, sidx, weights, E, C, bt, bc,
                        interpret)
    return out, (tokens, eidx, sidx, weights)


def _moe_dispatch_bwd(E, C, bt, bc, interpret, res, g):
    tokens, eidx, sidx, weights = res
    interpret = _resolve_interpret(interpret)
    # d tokens[t] = sum_k w[t,k] * g[e_k, s_k] — a combine of g
    safe_s = jnp.minimum(sidx, C - 1)
    valid = (sidx < C).astype(weights.dtype)
    dtok = _combine_raw(g, eidx, safe_s, weights * valid, bt,
                        DEFAULT_BC, interpret).astype(tokens.dtype)
    # d weights[t,k] = g[e_k, s_k] . tokens[t]
    gathered = g[eidx, safe_s].astype(jnp.float32)  # [T, K, M]
    dw = (gathered * tokens[:, None, :].astype(jnp.float32)).sum(-1)
    dw = (dw * valid.astype(jnp.float32)).astype(weights.dtype)
    return dtok, None, None, dw


moe_dispatch.defvjp(_moe_dispatch_fwd, _moe_dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def moe_combine(expert_out, eidx, sidx, weights, bt=DEFAULT_BT,
                bj=DEFAULT_BC, interpret=None):
    """Gather expert outputs back per token: out[t] = sum_k w[t,k] *
    expert_out[e_k, s_k].  Dropped choices (slot >= C) contribute 0."""
    interpret = _resolve_interpret(interpret)
    C = expert_out.shape[1]
    safe_s = jnp.minimum(sidx, C - 1)
    valid = (sidx < C).astype(weights.dtype)
    return _combine_raw(expert_out, eidx, safe_s, weights * valid, bt, bj,
                        interpret)


def _moe_combine_fwd(expert_out, eidx, sidx, weights, bt, bj, interpret):
    out = moe_combine(expert_out, eidx, sidx, weights, bt, bj, interpret)
    return out, (expert_out, eidx, sidx, weights)


def _moe_combine_bwd(bt, bj, interpret, res, g):
    expert_out, eidx, sidx, weights = res
    interpret = _resolve_interpret(interpret)
    E, C, M = expert_out.shape
    safe_s = jnp.minimum(sidx, C - 1)
    valid = (sidx < C).astype(weights.dtype)
    d_eo = _dispatch_raw(g, eidx, safe_s, weights * valid, E, C, bt,
                         DEFAULT_BC, interpret).astype(expert_out.dtype)
    gathered = expert_out[eidx, safe_s].astype(jnp.float32)
    dw = (gathered * g[:, None, :].astype(jnp.float32)).sum(-1)
    dw = (dw * valid.astype(jnp.float32)).astype(weights.dtype)
    return d_eo, None, None, dw


moe_combine.defvjp(_moe_combine_fwd, _moe_combine_bwd)
