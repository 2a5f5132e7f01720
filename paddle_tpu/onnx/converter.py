# lint-tpu: disable-file=L004 -- grandfathered direct jax use; new backend code belongs under core/ ops/ kernels/ static/ distributed/ (README: Repo lint)
"""jaxpr -> ONNX GraphProto conversion.

The exporter traces the model with `jax.make_jaxpr` (static shapes, the
same tracing contract as jit.to_static) and maps each jaxpr primitive to
ONNX ops (default opset 13).  Model parameters enter the jaxpr as consts
and become ONNX initializers, so the exported file is self-contained.

Reference behavior being replaced: python/paddle/onnx/export.py delegates
to the external paddle2onnx converter over a static Program; here the
traced jaxpr plays the Program's role and the converter is in-tree.
"""
from __future__ import annotations

import numpy as np
from jax.extend.core import Literal

from . import _pb

_DTYPE = {
    "float32": 1, "uint8": 2, "int8": 3, "uint16": 4, "int16": 5,
    "int32": 6, "int64": 7, "bool": 9, "float16": 10, "float64": 11,
    "uint32": 12, "uint64": 13, "bfloat16": 16,
}


def _onnx_dtype(np_dtype) -> int:
    name = np.dtype(np_dtype).name if np.dtype(np_dtype).name in _DTYPE \
        else str(np_dtype)
    try:
        return _DTYPE[name]
    except KeyError:
        raise NotImplementedError(f"ONNX export: unsupported dtype {np_dtype}")


def _tensor_proto(pb, name, arr):
    arr = np.asarray(arr)
    t = pb.TensorProto()
    t.name = name
    t.dims.extend(arr.shape)
    t.data_type = 16 if str(arr.dtype) == "bfloat16" \
        else _onnx_dtype(arr.dtype)
    t.raw_data = np.ascontiguousarray(arr).tobytes()
    return t


class _Graph:
    """Accumulates nodes/initializers with unique value names."""

    def __init__(self, pb, opset):
        self.pb = pb
        self.opset = opset
        self.nodes = []
        self.initializers = {}
        self._n = 0

    def fresh(self, hint="v"):
        self._n += 1
        return f"{hint}_{self._n}"

    def init(self, arr, hint="const"):
        name = self.fresh(hint)
        self.initializers[name] = _tensor_proto(self.pb, name, arr)
        return name

    def node(self, op_type, inputs, n_out=1, out_names=None, **attrs):
        node = self.pb.NodeProto()
        node.op_type = op_type
        node.name = self.fresh(op_type)
        node.input.extend(inputs)
        outs = out_names or [self.fresh(op_type.lower()) for _ in range(n_out)]
        node.output.extend(outs)
        for k, v in attrs.items():
            a = node.attribute.add()
            a.name = k
            if isinstance(v, float):
                a.type, a.f = self.pb.AttributeProto.FLOAT, v
            elif isinstance(v, bool) or isinstance(v, (int, np.integer)):
                a.type, a.i = self.pb.AttributeProto.INT, int(v)
            elif isinstance(v, str):
                a.type, a.s = self.pb.AttributeProto.STRING, v.encode()
            elif isinstance(v, (list, tuple)):
                if v and isinstance(v[0], float):
                    a.type = self.pb.AttributeProto.FLOATS
                    a.floats.extend(v)
                else:
                    a.type = self.pb.AttributeProto.INTS
                    a.ints.extend(int(x) for x in v)
            else:
                raise TypeError(f"attr {k}={v!r}")
        self.nodes.append(node)
        return outs[0] if n_out == 1 else outs


# --- primitive handlers ----------------------------------------------------
# each: fn(g, eqn, in_names) -> out_name(s)

_ELEMENTWISE = {
    "add": "Add", "sub": "Sub", "mul": "Mul", "div": "Div",
    "max": "Max", "min": "Min", "pow": "Pow",
    "exp": "Exp", "log": "Log", "tanh": "Tanh", "logistic": "Sigmoid",
    "sqrt": "Sqrt", "abs": "Abs", "neg": "Neg", "sign": "Sign",
    "floor": "Floor", "ceil": "Ceil", "round": "Round", "erf": "Erf",
    "sin": "Sin", "cos": "Cos", "tan": "Tan", "asin": "Asin",
    "acos": "Acos", "atan": "Atan", "sinh": "Sinh", "cosh": "Cosh",
    "not": "Not", "and": "And", "or": "Or", "xor": "Xor",
}

_COMPARE = {"eq": "Equal", "lt": "Less", "le": "LessOrEqual",
            "gt": "Greater", "ge": "GreaterOrEqual"}

_REDUCE_ATTR = {"reduce_max": "ReduceMax", "reduce_min": "ReduceMin",
                "reduce_prod": "ReduceProd"}


def _dot_general(g, eqn, ins):
    ((lc, rc), (lb, rb)) = eqn.params["dimension_numbers"]
    lhs, rhs = eqn.invars
    ln, rn = len(lhs.aval.shape), len(rhs.aval.shape)
    letters = iter("abcdefghijklmnopqrstuvwxyz")
    l_sub = [None] * ln
    r_sub = [None] * rn
    for i, j in zip(lb, rb):
        c = next(letters)
        l_sub[i] = c
        r_sub[j] = c
    for i, j in zip(lc, rc):
        c = next(letters)
        l_sub[i] = c
        r_sub[j] = c
    l_free = []
    for i in range(ln):
        if l_sub[i] is None:
            l_sub[i] = next(letters)
            l_free.append(l_sub[i])
    r_free = []
    for j in range(rn):
        if r_sub[j] is None:
            r_sub[j] = next(letters)
            r_free.append(r_sub[j])
    out_sub = [l_sub[i] for i in lb] + l_free + r_free
    eqstr = f"{''.join(l_sub)},{''.join(r_sub)}->{''.join(out_sub)}"
    return g.node("Einsum", ins, equation=eqstr)


def _conv(g, eqn, ins):
    p = eqn.params
    dn = p["dimension_numbers"]
    lhs_spec, rhs_spec, out_spec = dn
    nsp = len(lhs_spec) - 2
    # transpose input to NC<spatial>, kernel to OI<spatial>
    x = g.node("Transpose", [ins[0]], perm=list(lhs_spec))
    w = g.node("Transpose", [ins[1]], perm=list(rhs_spec))
    pads_lo = [int(lo) for lo, _ in p["padding"]]
    pads_hi = [int(hi) for _, hi in p["padding"]]
    lhs_dil = [int(d) for d in p["lhs_dilation"]]
    in_shape = [int(s) for s in eqn.invars[0].aval.shape]
    sp = [in_shape[lhs_spec[2 + i]] for i in range(nsp)]
    if any(d != 1 for d in lhs_dil):
        # transposed conv (conv2d_transpose lowers to conv_general_dilated
        # with lhs_dilation = stride; the kernel flip is an upstream rev
        # eqn by jaxpr time).  ONNX has no lhs_dilation, so zero-stuff the
        # input explicitly: [..,S,..] -> [..,S,1,..] -> pad -> [..,S*L,..]
        # -> slice off the (L-1) trailing zeros -> plain Conv.
        n_b, c_in = in_shape[lhs_spec[0]], in_shape[lhs_spec[1]]
        inter = [n_b, c_in]
        for s in sp:
            inter += [s, 1]
        x = g.node("Reshape", [x, g.init(
            np.asarray(inter, np.int64), "stuff_shape")])
        ndim = 2 + 2 * nsp
        pad_vec = [0] * (2 * ndim)
        for i, d in enumerate(lhs_dil):
            pad_vec[ndim + 3 + 2 * i] = d - 1  # after-pad the 1-dims
        x = g.node("Pad", [x, g.init(
            np.asarray(pad_vec, np.int64), "stuff_pads")])
        x = g.node("Reshape", [x, g.init(np.asarray(
            [n_b, c_in] + [s * d for s, d in zip(sp, lhs_dil)],
            np.int64), "stuffed")])
        sp = [(s - 1) * d + 1 for s, d in zip(sp, lhs_dil)]
        x = g.node("Slice", [
            x,
            g.init(np.asarray([0] * nsp, np.int64), "st"),
            g.init(np.asarray(sp, np.int64), "en"),
            g.init(np.asarray([2 + i for i in range(nsp)], np.int64),
                   "ax"),
            g.init(np.asarray([1] * nsp, np.int64), "sp")])
    if any(v < 0 for v in pads_lo + pads_hi):
        # XLA allows negative conv padding (transposed conv with padding
        # > kernel-1); ONNX Conv does not — crop with Slice first
        starts = [max(0, -lo) for lo in pads_lo]
        ends = [s - max(0, -hi) for s, hi in zip(sp, pads_hi)]
        x = g.node("Slice", [
            x,
            g.init(np.asarray(starts, np.int64), "nst"),
            g.init(np.asarray(ends, np.int64), "nen"),
            g.init(np.asarray([2 + i for i in range(nsp)], np.int64),
                   "nax"),
            g.init(np.asarray([1] * nsp, np.int64), "nsp")])
        pads_lo = [max(0, v) for v in pads_lo]
        pads_hi = [max(0, v) for v in pads_hi]
    out = g.node(
        "Conv", [x, w],
        strides=[int(s) for s in p["window_strides"]],
        pads=pads_lo + pads_hi,
        dilations=[int(d) for d in p["rhs_dilation"]],
        group=int(p["feature_group_count"]))
    # out currently NC<spatial>; permute to out_spec
    inv = [0] * (nsp + 2)
    for pos, axis in enumerate(out_spec):
        inv[axis] = pos
    return g.node("Transpose", [out], perm=inv)


def _pool(g, eqn, ins, kind):
    p = eqn.params
    win = list(p["window_dimensions"])
    strides = list(p["window_strides"])
    padding = list(p["padding"])
    w_dil = list(p.get("window_dilation", [1] * len(win)))
    if any(d != 1 for d in p.get("base_dilation", [1] * len(win))):
        raise NotImplementedError("ONNX export: base-dilated pooling")
    if win[0] != 1 or win[1] != 1 or w_dil[0] != 1 or w_dil[1] != 1:
        raise NotImplementedError(
            "ONNX export: reduce_window over batch/channel dims")
    kernel = [int(w) for w in win[2:]]
    dil = [int(d) for d in w_dil[2:]]
    pads_lo = [int(lo) for lo, _ in padding[2:]]
    pads_hi = [int(hi) for _, hi in padding[2:]]
    attrs = dict(kernel_shape=kernel, strides=[int(s) for s in strides[2:]],
                 pads=pads_lo + pads_hi)
    if any(d != 1 for d in dil):
        if kind != "max":
            # AveragePool only gained `dilations` at opset 19; this
            # converter declares <= 17, so emitting it would produce a
            # schema-invalid file that only the in-tree runtime accepts
            raise NotImplementedError(
                "ONNX export: dilated sum/avg pooling needs opset 19 "
                "(AveragePool dilations); only dilated MaxPool is "
                "supported at the declared opset")
        # ONNX MaxPool dilations attribute (opset 10+)
        attrs["dilations"] = dil
    if kind == "max":
        return g.node("MaxPool", ins, **attrs)
    # sum pool: AveragePool with zero-padding counted, times window size
    avg = g.node("AveragePool", ins, count_include_pad=1, **attrs)
    scale = g.init(np.asarray(float(np.prod(kernel)),
                              _np_dtype_of(eqn.invars[0])), "winsize")
    return g.node("Mul", [avg, scale])


def _np_dtype_of(var):
    return np.dtype(var.aval.dtype)


def _broadcast_in_dim(g, eqn, ins):
    p = eqn.params
    shape = [int(s) for s in p["shape"]]
    bdims = list(p["broadcast_dimensions"])
    in_shape = list(eqn.invars[0].aval.shape)
    interim = [1] * len(shape)
    for src, dst in enumerate(bdims):
        interim[dst] = in_shape[src]
    x = ins[0]
    if interim != in_shape:
        x = g.node("Reshape",
                   [x, g.init(np.asarray(interim, np.int64), "shape")])
    if interim != shape:
        x = g.node("Expand",
                   [x, g.init(np.asarray(shape, np.int64), "shape")])
    elif interim == in_shape:
        x = g.node("Identity", [x])
    return x


def _reshapeish(g, eqn, ins, new_shape):
    return g.node(
        "Reshape",
        [ins[0], g.init(np.asarray([int(s) for s in new_shape], np.int64),
                        "shape")])


def _gather(g, eqn, ins):
    """Simple take-along-one-axis gathers only (embedding lookups, x[idx])."""
    p = eqn.params
    dn = p["dimension_numbers"]
    operand = eqn.invars[0].aval
    slice_sizes = list(p["slice_sizes"])
    start_map = list(dn.start_index_map)
    collapsed = list(dn.collapsed_slice_dims)
    if len(start_map) == 1 and collapsed == start_map and \
            slice_sizes[start_map[0]] == 1 and \
            all(slice_sizes[d] == operand.shape[d]
                for d in range(len(slice_sizes)) if d != start_map[0]) and \
            not getattr(dn, "operand_batching_dims", ()):
        axis = start_map[0]
        idx = ins[1]
        # jax indices carry a trailing unit coordinate dim; drop it
        idx_shape = list(eqn.invars[1].aval.shape)
        if idx_shape and idx_shape[-1] == 1:
            idx = g.node("Reshape",
                         [idx, g.init(np.asarray(idx_shape[:-1] or [1],
                                                 np.int64), "shape")])
        out = g.node("Gather", [ins[0], idx], axis=axis)
        out_shape = [int(s) for s in eqn.outvars[0].aval.shape]
        return g.node("Reshape",
                      [out, g.init(np.asarray(out_shape, np.int64), "shape")])
    raise NotImplementedError(
        "ONNX export: general lax.gather (only single-axis take/embedding "
        "patterns are supported)")


class Converter:
    def __init__(self, opset: int = 13):
        if not 13 <= opset <= 17:
            raise NotImplementedError(
                f"ONNX export emits opset 13-17 op forms (ReduceSum/Slice "
                f"take tensor inputs; ReduceMax/Min/Prod still use the axes "
                f"attribute, removed in opset 18); opset_version={opset} "
                f"would produce an invalid model")
        self.pb = _pb.get()
        self.opset = opset

    # -- public --
    def convert(self, closed_jaxpr, input_names, graph_name="paddle_tpu"):
        pb = self.pb
        g = _Graph(pb, self.opset)
        jaxpr = closed_jaxpr.jaxpr
        env = {}

        for name, var in zip(input_names, jaxpr.invars):
            env[var] = name
        for var, const in zip(jaxpr.constvars, closed_jaxpr.consts):
            env[var] = g.init(np.asarray(const), "param")

        self._convert_eqns(g, jaxpr.eqns, env)

        graph = pb.GraphProto()
        graph.name = graph_name
        for name, var in zip(input_names, jaxpr.invars):
            graph.input.append(self._value_info(name, var.aval))
        out_names = []
        for i, var in enumerate(jaxpr.outvars):
            src = self._read(g, env, var)
            out = f"output_{i}"
            g.node("Identity", [src], out_names=[out])
            graph.output.append(self._value_info(out, var.aval))
            out_names.append(out)
        graph.node.extend(g.nodes)
        graph.initializer.extend(g.initializers.values())

        model = pb.ModelProto()
        model.ir_version = 8
        model.producer_name = "paddle_tpu"
        op = model.opset_import.add()
        op.domain = ""
        op.version = self.opset
        model.graph.CopyFrom(graph)
        return model

    # -- internals --
    def _value_info(self, name, aval):
        vi = self.pb.ValueInfoProto()
        vi.name = name
        tt = vi.type.tensor_type
        tt.elem_type = _onnx_dtype(aval.dtype)
        for s in aval.shape:
            tt.shape.dim.add().dim_value = int(s)
        return vi

    def _read(self, g, env, var):
        if isinstance(var, Literal):
            return g.init(np.asarray(var.val), "lit")
        return env[var]

    def _convert_eqns(self, g, eqns, env):
        for eqn in eqns:
            prim = eqn.primitive.name
            ins = [self._read(g, env, v) for v in eqn.invars]
            outs = self._emit(g, eqn, prim, ins, env)
            if not isinstance(outs, (list, tuple)):
                outs = [outs]
            for var, name in zip(eqn.outvars, outs):
                env[var] = name

    def _inline(self, g, eqn, ins, env, closed=None, open_jaxpr=None,
                consts=()):
        sub_env = {}
        jaxpr = closed.jaxpr if closed is not None else open_jaxpr
        sub_consts = closed.consts if closed is not None else consts
        for var, const in zip(jaxpr.constvars, sub_consts):
            sub_env[var] = g.init(np.asarray(const), "param")
        for var, name in zip(jaxpr.invars, ins):
            sub_env[var] = name
        self._convert_eqns(g, jaxpr.eqns, sub_env)
        return [self._read(g, sub_env, v) for v in jaxpr.outvars]

    def _emit(self, g, eqn, prim, ins, env):
        p = eqn.params
        pb = self.pb

        # --- structural / call primitives ---
        if prim in ("jit", "closed_call", "core_call",
                    "custom_vjp_call", "custom_jvp_call", "remat",
                    "checkpoint", "custom_vjp_call_jaxpr", "remat2"):
            closed = p.get("jaxpr") or p.get("call_jaxpr") or \
                p.get("fun_jaxpr")
            if closed is None:
                raise NotImplementedError(f"ONNX export: {prim} w/o jaxpr")
            if hasattr(closed, "consts"):
                return self._inline(g, eqn, ins, env, closed=closed)
            return self._inline(g, eqn, ins, env, open_jaxpr=closed)

        if prim in _ELEMENTWISE:
            return g.node(_ELEMENTWISE[prim], ins)
        if prim in _COMPARE:
            return g.node(_COMPARE[prim], ins)
        if prim == "ne":
            return g.node("Not", [g.node("Equal", ins)])
        if prim == "erfc":
            one = g.init(np.asarray(1, _np_dtype_of(eqn.invars[0])), "one")
            return g.node("Sub", [one, g.node("Erf", ins)])
        if prim == "rsqrt":
            return g.node("Reciprocal", [g.node("Sqrt", ins)])
        if prim == "log1p":
            one = g.init(np.asarray(1, _np_dtype_of(eqn.invars[0])), "one")
            return g.node("Log", [g.node("Add", [ins[0], one])])
        if prim == "expm1":
            one = g.init(np.asarray(1, _np_dtype_of(eqn.invars[0])), "one")
            return g.node("Sub", [g.node("Exp", ins), one])
        if prim == "integer_pow":
            expo = g.init(np.asarray(p["y"], _np_dtype_of(eqn.invars[0])),
                          "expo")
            return g.node("Pow", [ins[0], expo])
        if prim == "square":
            return g.node("Mul", [ins[0], ins[0]])
        if prim == "rem":
            return g.node("Mod", ins, fmod=1)
        if prim in ("stop_gradient", "copy", "device_put", "convert_layout"):
            return g.node("Identity", [ins[0]])
        if prim == "convert_element_type":
            return g.node("Cast", [ins[0]],
                          to=_onnx_dtype(np.dtype(p["new_dtype"])))
        if prim == "select_n":
            if len(ins) != 3:
                raise NotImplementedError("ONNX export: select_n with >2 cases")
            return g.node("Where", [ins[0], ins[2], ins[1]])
        if prim == "clamp":
            # jax clamp(min, x, max); general broadcast via Max/Min pair
            return g.node("Min", [g.node("Max", [ins[1], ins[0]]), ins[2]])
        if prim == "transpose":
            return g.node("Transpose", [ins[0]],
                          perm=list(p["permutation"]))
        if prim == "reshape":
            return _reshapeish(g, eqn, ins, eqn.outvars[0].aval.shape)
        if prim == "squeeze":
            return _reshapeish(g, eqn, ins, eqn.outvars[0].aval.shape)
        if prim == "expand_dims":
            return _reshapeish(g, eqn, ins, eqn.outvars[0].aval.shape)
        if prim == "broadcast_in_dim":
            return _broadcast_in_dim(g, eqn, ins)
        if prim == "concatenate":
            return g.node("Concat", ins, axis=int(p["dimension"]))
        if prim == "slice":
            if p.get("strides") is None:
                strides = [1] * len(p["start_indices"])
            else:
                strides = list(p["strides"])
            n = len(p["start_indices"])
            return g.node(
                "Slice",
                [ins[0],
                 g.init(np.asarray(p["start_indices"], np.int64), "starts"),
                 g.init(np.asarray(p["limit_indices"], np.int64), "ends"),
                 g.init(np.asarray(range(n), np.int64), "axes"),
                 g.init(np.asarray(strides, np.int64), "steps")])
        if prim == "rev":
            dims = list(p["dimensions"])
            n = len(dims)
            return g.node(
                "Slice",
                [ins[0],
                 g.init(np.full(n, -1, np.int64), "starts"),
                 g.init(np.full(n, np.iinfo(np.int64).min, np.int64), "ends"),
                 g.init(np.asarray(dims, np.int64), "axes"),
                 g.init(np.full(n, -1, np.int64), "steps")])
        if prim == "pad":
            cfg = p["padding_config"]
            if any(i != 0 for _, _, i in cfg):
                raise NotImplementedError("ONNX export: interior padding")
            if any(lo < 0 or hi < 0 for lo, hi, _ in cfg):
                raise NotImplementedError("ONNX export: negative padding")
            pads = [int(lo) for lo, _, _ in cfg] + \
                   [int(hi) for _, hi, _ in cfg]
            return g.node(
                "Pad",
                [ins[0], g.init(np.asarray(pads, np.int64), "pads"), ins[1]])
        if prim == "iota":
            dt = np.dtype(p["dtype"])
            shape = tuple(int(s) for s in p["shape"])
            dim = int(p["dimension"])
            idx = np.arange(shape[dim], dtype=dt)
            arr = np.broadcast_to(
                idx.reshape([-1 if i == dim else 1
                             for i in range(len(shape))]), shape)
            return g.node("Identity", [g.init(np.ascontiguousarray(arr),
                                              "iota")])
        if prim == "reduce_sum":
            return g.node(
                "ReduceSum",
                [ins[0], g.init(np.asarray(p["axes"], np.int64), "axes")],
                keepdims=0)
        if prim in _REDUCE_ATTR:
            return g.node(_REDUCE_ATTR[prim], ins,
                          axes=list(p["axes"]), keepdims=0)
        if prim in ("reduce_and", "reduce_or"):
            x = g.node("Cast", [ins[0]], to=2)  # uint8
            red = "ReduceMin" if prim == "reduce_and" else "ReduceMax"
            x = g.node(red, [x], axes=list(p["axes"]), keepdims=0)
            return g.node("Cast", [x], to=9)
        if prim in ("argmax", "argmin"):
            axes = p["axes"]
            if len(axes) != 1:
                raise NotImplementedError("ONNX export: multi-axis argmax")
            op = "ArgMax" if prim == "argmax" else "ArgMin"
            out = g.node(op, ins, axis=int(axes[0]), keepdims=0)
            want = _onnx_dtype(np.dtype(p["index_dtype"]))
            if want != 7:
                out = g.node("Cast", [out], to=want)
            return out
        if prim == "cumsum":
            axis = g.init(np.asarray(p["axis"], np.int64), "axis")
            return g.node("CumSum", [ins[0], axis],
                          reverse=1 if p.get("reverse") else 0)
        if prim == "reduce_window_max":
            return _pool(g, eqn, ins, "max")
        if prim == "reduce_window_sum":
            return _pool(g, eqn, ins, "sum")
        if prim == "conv_general_dilated":
            return _conv(g, eqn, ins)
        if prim == "dot_general":
            return _dot_general(g, eqn, ins)
        if prim == "gather":
            return _gather(g, eqn, ins)
        if prim == "is_finite":
            inf = g.node("IsInf", [ins[0]])
            nan = g.node("IsNaN", [ins[0]])
            return g.node("Not", [g.node("Or", [inf, nan])])
        if prim == "sort":
            raise NotImplementedError(
                "ONNX export: lax.sort (use topk-based ops)")
        raise NotImplementedError(
            f"ONNX export: jaxpr primitive {prim!r} has no ONNX mapping yet")
