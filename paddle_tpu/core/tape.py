"""Autograd tape: GradNode graph + backward engine.

TPU-native analog of the reference eager autograd
(/root/reference/paddle/fluid/eager/backward.cc:529 RunBackward,
grad_node_info.h:165 GradNodeBase, imperative/basic_engine.cc:267
PrepareDeps): reverse traversal with dependency counting and cotangent
accumulation.  Each GradNode owns one jax VJP closure (residuals = saved
tensors, the TensorWrapper analog); processing a node frees its residuals
unless retain_graph is set.
"""
from __future__ import annotations

from collections import deque
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

try:    # without it the backward only loses its labels in a trace
    from jax.extend.source_info_util import (current_name_stack,
                                             set_name_stack)
except ImportError:
    current_name_stack = set_name_stack = None


def _open_scope():
    if current_name_stack is None:
        return None
    scope = current_name_stack()
    return scope if scope.stack else None


def _zero_cotangent(shape, dtype):
    if jnp.issubdtype(dtype, jnp.inexact):
        return jnp.zeros(shape, dtype)
    return np.zeros(shape, jax.dtypes.float0)


class GradNode:
    """One recorded op: holds the vjp closure and links to producer nodes."""

    __slots__ = (
        "name",
        "vjp_fn",
        "out_avals",
        "single_output",
        "pending",
        "edges",
        "out_hooks",
        "input_tensors",
        "input_versions",
        "grad_raw_fn",
        "record_vjp",
        "scope",
        "__weakref__",
    )

    def __init__(self, name: str, vjp_fn):
        self.name = name
        self.vjp_fn = vjp_fn
        # the forward's ``jax.named_scope`` stack (None outside any):
        # the backward sweep re-enters it, so that a part's backward
        # operations carry the part's name in a trace as its forward's do
        self.scope = _open_scope()
        self.out_avals: List[Tuple[tuple, Any]] = []
        self.single_output = True
        self.pending: Optional[List[Any]] = None
        # edges[i] corresponds to the i-th differentiable input:
        #   ("node", producer_node, out_index) or ("leaf", tensor)
        self.edges: List[tuple] = []
        self.out_hooks: Dict[int, list] = {}
        # double-grad support (reference GeneralGrad + double-grad ops,
        # /root/reference/paddle/fluid/eager/backward.cc:37): the recorded
        # op's pure function + its differentiable input Tensors, so a
        # create_graph sweep can re-run the vjp THROUGH dispatch and give
        # the cotangents their own grad nodes.  Memory note: raw_fn's
        # closure (and these Tensor refs) pin the op's inputs for the
        # node's lifetime — for most ops the jax vjp residuals already do;
        # the increment is limited to residual-free ops (add & co) and is
        # bounded by the graph's lifetime (released after backward).
        self.input_tensors: Optional[List[Any]] = None
        self.input_versions: Optional[List[int]] = None
        self.grad_raw_fn = None
        self.record_vjp = None  # custom recordable vjp (PyLayer)

    def finalize(self, out_avals, single_output, inputs):
        self.out_avals = out_avals
        self.single_output = single_output
        self.pending = [None] * len(out_avals)
        self.input_tensors = list(inputs)
        self.input_versions = [t._version for t in inputs]
        for t in inputs:
            if t._grad_node is not None:
                self.edges.append(("node", t._grad_node, t._output_index))
            else:
                self.edges.append(("leaf", t))

    def accumulate(self, idx: int, cotangent):
        if self.pending[idx] is None:
            self.pending[idx] = cotangent
        else:
            self.pending[idx] = self.pending[idx] + cotangent

    def assembled_cotangents(self, as_tensor=False):
        cots = []
        for i, (shape, dtype) in enumerate(self.out_avals):
            c = self.pending[i]
            if c is None:
                c = _zero_cotangent(shape, dtype)
                if as_tensor:  # float0 zeros wrap too: PyLayer backward's
                    c = _wrap(c)  # contract is Tensors for every cotangent
            for hook in self.out_hooks.get(i, ()):
                out = hook(_wrap(c))
                if out is not None:
                    c = out if as_tensor else _unwrap(out)
            cots.append(c)
        return cots

    def check_versions(self):
        """Raise if any input was mutated in place after recording
        (reference: eager VariableWrapper inplace_version check)."""
        if not self.input_tensors:
            return
        for t, v0 in zip(self.input_tensors, self.input_versions):
            if t._version != v0:
                raise RuntimeError(
                    f"a tensor consumed by op '{self.name}' was modified "
                    f"by an inplace operation after being recorded "
                    f"(version {t._version} vs {v0}); gradients would be "
                    "wrong — clone() before mutating, or mutate after "
                    "backward")

    def release(self):
        self.vjp_fn = None
        self.pending = [None] * len(self.out_avals)
        self.input_tensors = None
        self.input_versions = None
        self.grad_raw_fn = None
        self.record_vjp = None


def _wrap(raw):
    from .tensor import Tensor

    if isinstance(raw, Tensor):
        return raw
    return Tensor(raw, stop_gradient=True)


def _unwrap(t):
    from .tensor import Tensor

    return t._value if isinstance(t, Tensor) else t


def _cot_dtype(c):
    from .tensor import Tensor

    return c._value.dtype if isinstance(c, Tensor) else c.dtype


def _accumulate_leaf_grad(tensor, cotangent):
    from .tensor import Tensor

    c = cotangent
    for hook in tensor._hooks:
        out = hook(_wrap(c))
        if out is not None:
            c = out if isinstance(cotangent, Tensor) else _unwrap(out)
    if isinstance(c, Tensor):  # create_graph sweep: grads keep their graph
        tensor.grad = c if tensor.grad is None else tensor.grad + c
    elif tensor.grad is None:
        tensor.grad = Tensor(c, stop_gradient=True)
    else:
        tensor.grad = Tensor(tensor.grad._value + c, stop_gradient=True)


def _record_vjp_via_apply(node, cot_tensors):
    """Compute node's vjp THROUGH dispatch so the resulting cotangents are
    themselves recorded (the double-grad op of the reference's codegen'd
    GradNode pairs).  Re-runs the op's forward for the residuals — the
    standard recompute formulation of grad-of-grad."""
    from . import dispatch

    raw_fn = node.grad_raw_fn
    n_in = len(node.input_tensors)
    out_avals = node.out_avals
    single = node.single_output
    inexact = [i for i, (_, d) in enumerate(out_avals)
               if jnp.issubdtype(d, jnp.inexact)]
    passed = [cot_tensors[i] for i in inexact]

    def op(*vals):
        primals, cvals = vals[:n_in], list(vals[n_in:])
        cots = []
        for i, (shape, dtype) in enumerate(out_avals):
            if jnp.issubdtype(dtype, jnp.inexact):
                cots.append(cvals.pop(0))
            else:
                cots.append(np.zeros(shape, jax.dtypes.float0))
        _, vjp = jax.vjp(raw_fn, *primals)
        return tuple(vjp(cots[0] if single else tuple(cots)))

    with dispatch.enable_grad_ctx():
        res = dispatch.apply(f"{node.name}_grad", op,
                             *node.input_tensors, *passed)
    return list(res) if isinstance(res, tuple) else [res]


def _discover(roots):
    """BFS the node graph; return (all nodes, in-degree per node)."""
    in_deg: Dict[int, int] = {}
    nodes: Dict[int, GradNode] = {}
    stack = list(roots)
    seen = set()
    while stack:
        node = stack.pop()
        if id(node) in seen:
            continue
        seen.add(id(node))
        nodes[id(node)] = node
        for kind, *rest in node.edges:
            if kind == "node":
                prod = rest[0]
                in_deg[id(prod)] = in_deg.get(id(prod), 0) + 1
                stack.append(prod)
    return nodes, in_deg


def run_backward(tensors, grad_tensors=None, retain_graph=False,
                 capture: Optional[Dict[int, Any]] = None,
                 capture_points: Optional[Dict[Tuple[int, int], list]] = None,
                 create_graph: bool = False):
    """Reverse-mode sweep from `tensors`.

    capture/capture_points support the functional paddle.grad API: when a
    target tensor is an intermediate, its fully-assembled cotangent is
    recorded at (producer node, output index) processing time.

    create_graph: cotangents flow as Tensors and each node's vjp runs
    THROUGH dispatch (recorded), so the produced gradients are themselves
    differentiable (reference: eager double-grad ops + GeneralGrad,
    backward.cc:37).  Implies retain_graph.
    """
    from .tensor import Tensor

    if create_graph:
        retain_graph = True
    if isinstance(tensors, Tensor):
        tensors = [tensors]
    if grad_tensors is None:
        grad_tensors = [None] * len(tensors)
    elif isinstance(grad_tensors, Tensor):
        grad_tensors = [grad_tensors]

    roots = []
    for t, g in zip(tensors, grad_tensors):
        if g is None:
            g_val = jnp.ones(t.shape, t._value.dtype)
            if create_graph:
                g_val = _wrap(g_val)
        elif create_graph:
            g_val = g if isinstance(g, Tensor) else _wrap(jnp.asarray(g))
        else:
            g_val = g._value if isinstance(g, Tensor) else jnp.asarray(g)
        node = t._grad_node
        if node is None:
            if not t.stop_gradient:
                _accumulate_leaf_grad(t, g_val)
            continue
        if node.vjp_fn is None:
            raise RuntimeError(
                "Trying to run backward through the graph a second time "
                "(pass retain_graph=True the first time)."
            )
        node.accumulate(t._output_index, g_val)
        roots.append(node)

    if not roots:
        return

    nodes, in_deg = _discover(roots)
    queue = deque(n for n in nodes.values() if in_deg.get(id(n), 0) == 0)
    processed = set()

    # create_graph: the whole sweep (cotangent adds included) must record,
    # even when the caller sits inside no_grad.
    import contextlib

    from . import dispatch

    grad_ctx = (dispatch.enable_grad_ctx() if create_graph
                else contextlib.nullcontext())
    with grad_ctx:
        while queue:
            node = queue.popleft()
            if id(node) in processed:
                continue
            processed.add(id(node))

            cots = node.assembled_cotangents(as_tensor=create_graph)
            if capture_points:
                for (nid, idx), sinks in capture_points.items():
                    if nid == id(node):
                        for sink in sinks:
                            capture[sink] = cots[idx]
            if node.vjp_fn is None:
                raise RuntimeError(
                    f"grad node {node.name} already released; use "
                    "retain_graph=True")
            node.check_versions()
            if create_graph:
                if node.record_vjp is not None:
                    in_cots = node.record_vjp(cots)
                elif node.grad_raw_fn is not None and \
                        node.input_tensors is not None:
                    in_cots = _record_vjp_via_apply(node, cots)
                else:
                    raise RuntimeError(
                        f"op '{node.name}' does not support create_graph "
                        "(no recordable vjp)")
            else:
                cot = cots[0] if node.single_output else tuple(cots)
                if node.scope is None:
                    in_cots = node.vjp_fn(cot)
                else:
                    with set_name_stack(node.scope):
                        in_cots = node.vjp_fn(cot)

            for (kind, *rest), cot in zip(node.edges, in_cots):
                if cot is None or _cot_dtype(cot) == jax.dtypes.float0:
                    continue
                if kind == "leaf":
                    tensor = rest[0]
                    if capture is not None:
                        if id(tensor) in capture:
                            prev = capture[id(tensor)]
                            capture[id(tensor)] = (cot if prev is None
                                                   else prev + cot)
                        # else: functional grad (only_inputs) — never
                        # touch .grad of tensors outside `inputs`
                    else:
                        _accumulate_leaf_grad(tensor, cot)
                else:
                    prod, idx = rest
                    prod.accumulate(idx, cot)
                    in_deg[id(prod)] -= 1
                    if in_deg[id(prod)] == 0:
                        queue.append(prod)

            if not retain_graph:
                node.release()
            else:
                node.pending = [None] * len(node.out_avals)
