"""Eager op dispatch + grad recording.

This is the TPU-native replacement for the reference dygraph tracer
(/root/reference/paddle/fluid/imperative/tracer.cc:186 TraceOpImpl and the
eager engine /root/reference/paddle/fluid/eager/): every framework op is a
functional JAX computation; when gradients are required we obtain the op's
VJP closure via jax.vjp at call time (one forward execution, residuals live
on device) and record a GradNode on the tape.  There is exactly ONE autograd
engine — no legacy/eager split.

Inside `paddle_tpu.jit.to_static` traces the tape is bypassed entirely:
differentiation of compiled programs happens through jax.grad on the
functionalized program, which is the idiomatic XLA path.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, List

import jax
import jax.numpy as jnp

from . import tape as tape_mod
from .flags import flag


class _State(threading.local):
    def __init__(self):
        self.grad_enabled = True
        self.in_static_trace = False


_state = _State()

# Static-graph recorder hook: paddle_tpu.static.graph installs a callback
# while static mode is enabled; apply() routes ops that touch symbolic
# Variables to it (the reference's dygraph/static mode switch,
# /root/reference/python/paddle/fluid/framework.py in_dygraph_mode).
NOT_RECORDED = object()  # recorder return value meaning "run eagerly"
_graph_recorder = None


def set_graph_recorder(recorder):
    global _graph_recorder
    prev = _graph_recorder
    _graph_recorder = recorder
    return prev


def is_grad_enabled() -> bool:
    # NB: the tape keeps recording inside to_static traces — jax.vjp over
    # tracers is what lets loss.backward() + optimizer.step() compile into
    # the one traced program.  in_static_trace only gates data-dependent-shape
    # ops (nonzero/unique/...), which must raise under a trace.
    return _state.grad_enabled


def set_grad_enabled(mode: bool):
    _state.grad_enabled = bool(mode)


@contextlib.contextmanager
def no_grad_ctx():
    prev = _state.grad_enabled
    _state.grad_enabled = False
    try:
        yield
    finally:
        _state.grad_enabled = prev


@contextlib.contextmanager
def enable_grad_ctx():
    prev = _state.grad_enabled
    _state.grad_enabled = True
    try:
        yield
    finally:
        _state.grad_enabled = prev


@contextlib.contextmanager
def static_trace_guard():
    """Active while jit.to_static traces user code: tape off, ops trace into XLA."""
    prev = _state.in_static_trace
    _state.in_static_trace = True
    try:
        yield
    finally:
        _state.in_static_trace = prev


def in_static_trace() -> bool:
    return _state.in_static_trace


class no_grad:
    """Context manager AND decorator, like paddle.no_grad."""

    def __enter__(self):
        self._prev = _state.grad_enabled
        _state.grad_enabled = False
        return self

    def __exit__(self, *exc):
        _state.grad_enabled = self._prev
        return False

    def __call__(self, fn):
        import functools

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with no_grad_ctx():
                return fn(*args, **kwargs)

        return wrapper


_TENSOR_CLS = None  # lazy-cached: a per-op-call import is hot-path cost


def _tensor_cls():
    global _TENSOR_CLS
    if _TENSOR_CLS is None:
        from .tensor import Tensor

        _TENSOR_CLS = Tensor
    return _TENSOR_CLS


def _is_tensor(x):
    return isinstance(x, _tensor_cls())


_AMP_FN = None


def _amp_op_dtype_fn():
    """Cached ref to amp.amp_op_dtype (None until the amp module imports —
    a try/import per op call is hot-path cost)."""
    global _AMP_FN
    if _AMP_FN is None:
        try:
            from ..amp import amp_op_dtype

            _AMP_FN = amp_op_dtype
        except ImportError:  # during early package import
            return None
    return _AMP_FN


# dtypes are interned; cache differentiability per dtype instead of calling
# jnp.issubdtype/result_type on every op argument (eager hot path)
_DIFF_DTYPE_CACHE = {}


# ---------------------------------------------------------------------------
# Analytic eager VJP rules: jax.vjp re-linearizes the op on EVERY eager call
# (measured ~3050 us/op on this image's CPU for a 6-op fwd+bwd training
# chain vs ~250 us/op with the rules — 11.9x), which is pure
# overhead when the backward
# is a closed form.  We record the closed form directly and skip jax.vjp —
# the analog of the reference's codegen'd per-op GradNode pairs
# (imperative/tracer.cc TraceOpImpl + generated grad ops).  jax.vjp remains
# the fallback for everything else (and for double-grad, which re-derives
# through dispatch).  A rule fires only when `fn` IS the registered callable
# and the rule accepts the call's attrs — a same-named op with a different
# closure or unsupported attr combination falls back.  The hot-set rules
# (matmul/linear/reductions/activations/layer_norm/embedding/reshape/
# transpose) register from their op modules via register_eager_vjp.
def _unbroadcast(ct, shape, dtype):
    shape = tuple(shape)
    if ct.shape != shape:
        extra = ct.ndim - len(shape)
        if extra > 0:
            ct = ct.sum(axis=tuple(range(extra)))
        axes = tuple(i for i, s in enumerate(shape)
                     if s == 1 and ct.shape[i] != 1)
        if axes:
            ct = ct.sum(axis=axes, keepdims=True)
    if ct.dtype != dtype:
        ct = ct.astype(dtype)
    return ct


# name -> tuple of (impl_fn, rule).  rule(vals, attrs) returns
# (out, vjp_over_all_inputs) or None to fall back to jax.vjp for this
# particular call (unsupported attr combination, odd ranks, ...).
_EAGER_VJP_RULES = {}


def register_eager_vjp(name, impl_fn, rule, allow_containers=False):
    """Register a closed-form eager VJP for op `name` when dispatched with
    `impl_fn` (matched by identity — a same-named op arriving with a
    different closure falls back to jax.vjp).  Multiple impls may share a
    name (e.g. linear with/without bias).  With allow_containers the rule
    also fires for container-arg ops (concat/stack): it then receives the
    FLATTENED tensor leaves in pytree order."""
    _EAGER_VJP_RULES[name] = _EAGER_VJP_RULES.get(name, ()) + (
        (impl_fn, rule, allow_containers),)


def eager_binop_rule(fwd, bwd):
    def rule(vals, attrs):
        if attrs:
            return None
        a, b = vals
        out = fwd(a, b)

        def vjp(ct):
            ga, gb = bwd(ct, a, b, out)
            return (_unbroadcast(ga, a.shape, a.dtype),
                    _unbroadcast(gb, b.shape, b.dtype))
        return out, vjp
    return rule


def eager_unop_rule(fwd, bwd):
    def rule(vals, attrs):
        if attrs:
            return None
        (a,) = vals
        out = fwd(a)
        return out, lambda ct: (bwd(ct, a, out).astype(a.dtype),)
    return rule


def _silu_bwd(ct, a, o):
    # d/dx x*s(x) = s + x*s*(1-s) = s + o*(1-s)
    s = jax.nn.sigmoid(a)
    return ct * (s + o * (1.0 - s))


def _register_builtin_rules():
    unop, binop = eager_unop_rule, eager_binop_rule
    for name, impl, rule in (
        ("add", jnp.add, binop(jnp.add, lambda ct, a, b, o: (ct, ct))),
        ("subtract", jnp.subtract, binop(
            jnp.subtract, lambda ct, a, b, o: (ct, -ct))),
        ("multiply", jnp.multiply, binop(
            jnp.multiply, lambda ct, a, b, o: (ct * b, ct * a))),
        ("divide", jnp.divide, binop(
            jnp.divide, lambda ct, a, b, o: (ct / b, -ct * o / b))),
        ("exp", jnp.exp, unop(jnp.exp, lambda ct, a, o: ct * o)),
        ("log", jnp.log, unop(jnp.log, lambda ct, a, o: ct / a)),
        ("tanh", jnp.tanh, unop(
            jnp.tanh, lambda ct, a, o: ct * (1.0 - o * o))),
        ("sqrt", jnp.sqrt, unop(
            jnp.sqrt, lambda ct, a, o: ct * 0.5 / o)),
        ("rsqrt", jax.lax.rsqrt, unop(
            jax.lax.rsqrt, lambda ct, a, o: ct * -0.5 * o * o * o)),
        # activations dispatched with their jax.nn callable directly
        ("relu", jax.nn.relu, unop(
            jax.nn.relu, lambda ct, a, o: jnp.where(a > 0, ct, 0))),
        ("sigmoid", jax.nn.sigmoid, unop(
            jax.nn.sigmoid, lambda ct, a, o: ct * o * (1.0 - o))),
        ("silu", jax.nn.silu, unop(jax.nn.silu, _silu_bwd)),
        ("swish", jax.nn.silu, unop(jax.nn.silu, _silu_bwd)),
    ):
        register_eager_vjp(name, impl, rule)


_register_builtin_rules()


def _differentiable_dtype(v) -> bool:
    dt = getattr(v, "dtype", None)
    if dt is None:
        return jnp.issubdtype(jnp.result_type(v), jnp.inexact)
    hit = _DIFF_DTYPE_CACHE.get(dt)
    if hit is None:
        hit = _DIFF_DTYPE_CACHE[dt] = bool(
            jnp.issubdtype(dt, jnp.inexact))
    return hit


def apply(name: str, fn, *args, _differentiable: bool = True, **attrs):
    """Run op `fn` over args (Tensors possibly nested in lists/tuples) with
    static keyword attrs; wrap outputs in Tensors and record the grad node.
    """
    Tensor = _tensor_cls()

    if _graph_recorder is not None:
        rec = _graph_recorder(name, fn, args, attrs)
        if rec is not NOT_RECORDED:
            return rec

    # fast path: args with no containers skip the pytree machinery (the
    # overwhelmingly common case — reference hot loop analog TraceOpImpl).
    # ONE fused scan builds flat/tensor_idx/diff_idx: this wrapper is the
    # per-op eager hot loop (reference TraceOpImpl + PrepareImpl), and
    # the previous four generator passes over the args were ~40% of the
    # measured dispatch overhead.
    for a in args:
        if isinstance(a, (list, tuple, dict)):
            flat, treedef = jax.tree_util.tree_flatten(
                args, is_leaf=_is_tensor)
            break
    else:
        flat, treedef = list(args), None

    grad_on = _differentiable and _state.grad_enabled
    tensor_idx = []
    diff_idx = []
    for i, leaf in enumerate(flat):
        if isinstance(leaf, Tensor):
            tensor_idx.append(i)
            # differentiable leaves become vjp arguments, the rest are
            # closed over as constants
            if grad_on and not leaf.stop_gradient and \
                    _differentiable_dtype(leaf._value):
                diff_idx.append(i)
    record = bool(diff_idx)

    # AMP O1/O2: per-op cast decision (reference: imperative/tracer.cc:224
    # AutoCastInputs / amp_auto_cast.cc).  The cast happens inside raw_fn so
    # the vjp closure differentiates through it.
    amp_np_dtype = None
    amp_fn = _amp_op_dtype_fn()
    if amp_fn is not None:
        amp_target = amp_fn(name)
        if amp_target is not None:
            from .dtype import to_np

            amp_np_dtype = to_np(amp_target)

    def _amp_cast(v):
        if amp_np_dtype is not None and jnp.issubdtype(
                jnp.result_type(v), jnp.floating):
            return v.astype(amp_np_dtype)
        return v

    def raw_fn(*diff_vals):
        new_flat = list(flat)
        for pos, v in zip(diff_idx, diff_vals):
            new_flat[pos] = _amp_cast(v)
        for i in tensor_idx:
            if i not in diff_idx:
                new_flat[i] = _amp_cast(new_flat[i]._value)
        if treedef is None:
            return fn(*new_flat, **attrs)
        new_args = jax.tree_util.tree_unflatten(treedef, new_flat)
        return fn(*new_args, **attrs)

    if record:
        out_raw = None
        rule_entries = _EAGER_VJP_RULES.get(name)
        if (rule_entries is not None and amp_np_dtype is None
                and len(tensor_idx) == len(flat)):
            for impl_fn, rule, allow_containers in rule_entries:
                if impl_fn is fn and (treedef is None
                                      or allow_containers):
                    res = rule([t._value for t in flat], attrs)
                    if res is not None:
                        out_raw, vjp_all = res
                    break
        if out_raw is not None:
            if len(diff_idx) == len(flat):
                vjp_fn = vjp_all
            else:
                sel = tuple(diff_idx)

                def vjp_fn(ct, _v=vjp_all, _sel=sel):
                    gs = _v(ct)
                    return tuple(gs[i] for i in _sel)
        if out_raw is None:
            diff_vals = [flat[i]._value for i in diff_idx]
            out_raw, vjp_fn = jax.vjp(raw_fn, *diff_vals)
        node = tape_mod.GradNode(name, vjp_fn)
        node.grad_raw_fn = raw_fn  # double-grad: recordable vjp recompute
    else:
        out_raw = raw_fn()
        node = None

    single = not isinstance(out_raw, (tuple, list))
    out_list = [out_raw] if single else list(out_raw)

    outputs: List[Any] = []
    for i, o in enumerate(out_list):
        diff_out = record and _differentiable_dtype(o)
        t = Tensor(o, stop_gradient=not diff_out)
        if record:
            t._grad_node = node
            t._output_index = i
        outputs.append(t)

    if node is not None:
        node.finalize(
            out_avals=[(tuple(o.shape), o.dtype) for o in out_list],
            single_output=single,
            inputs=[flat[i] for i in diff_idx],
        )

    if flag("check_nan_inf"):
        _check_nan_inf(name, outputs)

    return outputs[0] if single else tuple(outputs)


def _check_nan_inf(name, outputs):
    """FLAGS_check_nan_inf analog (reference: details/nan_inf_utils_detail,
    hooked into every op run at operator.cc:1270).  Eager: host check.
    Compiled: a device-side finite-reduction feeds a debug callback that
    raises — the compiled-mode debug path the reference gets from its
    per-op nan/inf CUDA kernels."""
    import numpy as np

    for t in outputs:
        v = t._value
        if not jnp.issubdtype(v.dtype, jnp.inexact):
            continue
        if isinstance(v, jax.core.Tracer):
            ok = jnp.isfinite(v.astype(jnp.float32)).all()

            def _host_assert(ok_val, _name=name):
                if not bool(ok_val):
                    raise FloatingPointError(
                        f"op {_name} produced nan/inf (compiled mode)")

            jax.debug.callback(_host_assert, ok)
            continue
        arr = np.asarray(v.astype(jnp.float32))
        if not np.isfinite(arr).all():
            raise FloatingPointError(f"op {name} produced nan/inf")
