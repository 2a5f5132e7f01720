# lint-tpu: disable-file=L004 -- grandfathered direct jax use; new backend code belongs under core/ ops/ kernels/ static/ distributed/ (README: Repo lint)
"""paddle.jit analog: compile eager code to one XLA executable.

Replaces the reference dy2static stack
(/root/reference/python/paddle/fluid/dygraph/dygraph_to_static/
program_translator.py ProgramTranslator, ConcreteProgram input-spec cache,
partial_program.py) the TPU-native way: instead of AST-rewriting Python into
a ProgramDesc, the function is traced with JAX abstract values straight to
StableHLO and compiled by XLA.

What the trace captures as *program state* (inputs AND outputs):
  - every Parameter of the layers involved (so weight updates inside the
    traced fn — optimizer.step() — become functional outputs)
  - every Layer buffer (BN running stats etc.)
  - optimizer accumulator slots + device step counter
  - the RNG key (dropout draws fold_in from a per-call key input)
  - each optimizer's learning rate (a dynamic scalar input, so LR schedules
    don't retrace)

The eager tape keeps working inside the trace (jax.vjp over tracers), so a
whole train_step — forward, loss.backward(), optimizer.step() — compiles to
one fused XLA program.  Data-dependent Python control flow must use
paddle_tpu.jit.cond/while_loop/scan (→ XLA control flow), matching the
reference's static control-flow ops (fluid/layers/control_flow.py While:1024).
"""
from __future__ import annotations

import contextlib
import functools
import itertools
import types
from typing import Any, Dict, List

import jax
import jax.export  # noqa: F401 — jax.export is lazy; attribute access alone fails
import jax.numpy as jnp
import numpy as np

from ..core import dispatch
from ..core.dtype import to_np
from ..core.tensor import Tensor
from ..nn.layer.layers import Layer
from ..ops import random as rnd

__all__ = ["to_static", "not_to_static", "InputSpec", "save", "load", "cond",
           "while_loop", "scan", "StaticFunction"]


class InputSpec:
    """paddle.static.InputSpec analog."""

    def __init__(self, shape, dtype="float32", name=None, stop_gradient=True):
        self.shape = list(shape)
        self.dtype = dtype
        self.name = name
        self.stop_gradient = stop_gradient

    def __repr__(self):
        return f"InputSpec(shape={self.shape}, dtype={self.dtype})"


def _is_arrayish(v):
    return isinstance(v, (jnp.ndarray, np.ndarray)) or (
        hasattr(v, "aval") and hasattr(v, "dtype"))


@functools.lru_cache(maxsize=4096)
def _code_global_names(code) -> tuple:
    """Names a code object (incl. NESTED code objects) reads via
    LOAD_GLOBAL/LOAD_NAME.  A layer referenced only inside a local
    helper (`def body(i, acc): return i+1, acc+lin(x)`) is just as
    load-bearing as one named at the top level — missing it silently
    discards its weight updates AND leaks the trace tracer into the
    live param.  LOAD_GLOBAL only (co_names also holds attribute names,
    which must not pull in unrelated same-named globals).  Memoized per
    code object: callers run per jit.cond/while_loop/scan invocation."""
    import dis

    names, codes = [], [code]
    while codes:
        c = codes.pop()
        for ins in dis.get_instructions(c):
            if ins.opname in ("LOAD_GLOBAL", "LOAD_NAME"):
                names.append(ins.argval)
        codes.extend(k for k in c.co_consts
                     if isinstance(k, types.CodeType))
    seen, out = set(), []
    for n in names:
        if n not in seen:
            seen.add(n)
            out.append(n)
    return tuple(out)


def _referenced_objects(obj):
    """Objects a function can reach: bound self, closure cells, and the
    module globals its code names.  This is how the trace discovers which
    Layers/Optimizers hold state (the reference discovers them through
    ProgramTranslator's parameter recorder)."""
    out = []
    bound_self = getattr(obj, "__self__", None)
    if bound_self is not None:
        out.append(bound_self)
    fn = getattr(obj, "__func__", obj)
    code = getattr(fn, "__code__", None)
    if code is not None:
        g = getattr(fn, "__globals__", {})
        for name in _code_global_names(code):
            if name in g:
                out.append(g[name])
        for cell in (fn.__closure__ or ()):
            try:
                out.append(cell.cell_contents)
            except ValueError:
                pass
    for d in (getattr(fn, "__defaults__", None) or ()):
        out.append(d)
    return out


def _flatten_candidates(objs):
    flat = []
    for v in objs:
        flat.append(v)
        if isinstance(v, (list, tuple)):
            flat.extend(v)
        elif isinstance(v, dict):
            flat.extend(v.values())
    return flat


def _find_layers(obj, seen=None) -> List[Layer]:
    seen = seen if seen is not None else set()
    out = []
    if isinstance(obj, Layer):
        if id(obj) not in seen:
            seen.add(id(obj))
            out.append(obj)
        return out
    for v in _flatten_candidates(_referenced_objects(obj)):
        if isinstance(v, Layer) and id(v) not in seen:
            seen.add(id(v))
            out.append(v)
    return out


def _find_optimizers(obj) -> list:
    from ..optimizer.optimizer import Optimizer

    out = []
    seen = set()
    for v in _flatten_candidates(_referenced_objects(obj)):
        # meta-optimizer wrappers (GradientMerge/LocalSGD) hold the real
        # Optimizer as ._inner — unwrap so its state threads through
        hops = 0
        while not isinstance(v, Optimizer) and hops < 4 and \
                getattr(v, "_inner", None) is not None:
            v = v._inner
            hops += 1
        if isinstance(v, Optimizer) and id(v) not in seen:
            seen.add(id(v))
            out.append(v)
    return out


class _State:
    """Handles to every mutable array a trace must thread through."""

    def __init__(self, layers, optimizers):
        self.params: List[Tensor] = []
        self.buffers: List[Tensor] = []
        self._param_order: Dict[int, int] = {}
        seen = set()
        for layer in layers:
            for _, p in layer.named_parameters():
                if id(p) not in seen:
                    seen.add(id(p))
                    self.params.append(p)
            for _, b in layer.named_buffers():
                if id(b) not in seen:
                    seen.add(id(b))
                    self.buffers.append(b)
        self.optimizers = list(optimizers)
        # BARE tensors handed straight to an optimizer (no Layer) are
        # state too: reference scripts train plain
        # paddle.to_tensor(stop_gradient=False) params; without this,
        # opt.step() under trace writes a tracer into the live value and
        # the update is silently lost
        for opt in self.optimizers:
            for p in (getattr(opt, "_parameter_list", None) or ()):
                # parameter-GROUP dicts ({'params': [...], 'lr': ...})
                # hold bare tensors too (optimizer.py _static_minimize
                # flattens them the same way)
                entries = (p.get("params", []) if isinstance(p, dict)
                           else [p])
                for q in entries:
                    if isinstance(q, Tensor) and id(q) not in seen:
                        seen.add(id(q))
                        self.params.append(q)

    def opt_slots(self):
        # slots are keyed by id(param); walk them in PARAMETER order, not
        # id order: the order is the compiled program's argument order,
        # and a program that follows object addresses is a different
        # program every run — the persistent compile cache never hits
        if len(self._param_order) != len(self.params):
            self._param_order = {id(p): i
                                 for i, p in enumerate(self.params)}
        order, last = self._param_order, len(self.params)
        slots = []
        for opt in self.optimizers:
            for name in sorted(opt._accumulators):
                store = opt._accumulators[name]
                for pid in sorted(store,
                                  key=lambda k: (order.get(k, last), k)):
                    slots.append((store, pid))
            for key in sorted(opt._global_state):
                slots.append((opt._global_state, key))
        return slots

    def read(self):
        return ([p._value for p in self.params]
                + [b._value for b in self.buffers]
                + [store[k] for store, k in self.opt_slots()])

    def write(self, vals, slots=None):
        n_p, n_b = len(self.params), len(self.buffers)
        for p, v in zip(self.params, vals[:n_p]):
            p._value = v
            p.grad = None
            p._grad_node = None
        for b, v in zip(self.buffers, vals[n_p:n_p + n_b]):
            b._value = v
        slots = slots if slots is not None else self.opt_slots()
        for (store, k), v in zip(slots, vals[n_p + n_b:]):
            store[k] = v

    def signature(self):
        return (len(self.params), len(self.buffers),
                tuple((id(s), k) for s, k in self.opt_slots()))


def _spec_key(flat_static, treedef, dyn_leaves):
    dyn = tuple((tuple(v.shape), str(v.dtype)) for v in dyn_leaves)
    stat = tuple(
        v if isinstance(v, (int, float, bool, str, bytes, type(None)))
        else repr(v) for v in flat_static)
    return (dyn, stat, str(treedef))


class StaticFunction:
    """Compiled callable with an input-spec cache (the ConcreteProgram cache
    analog, reference: program_translator.py)."""

    def __init__(self, fn, input_spec=None, loop_max_trips=None, **unused):
        self._fn = fn
        self._traced_fn = None  # dy2static-converted clone, built lazily
        self._input_spec = input_spec
        # bound for tensor-condition python loops: lowers them to the
        # differentiable bounded while (scan-of-cond) so reference-style
        # training scripts with data-dependent loops work end to end
        self._loop_max_trips = loop_max_trips
        self._cache: Dict[Any, Any] = {}
        self._bound_cache: Dict[int, "StaticFunction"] = {}
        self._layers = None
        self._optimizers = None
        self._mode_layers = None
        self._state = None
        self._state_version = -1
        functools.update_wrapper(self, fn, updated=[])

    def _trace_target(self):
        """The function the tracer compiles: the AST-converted clone when
        the dy2static pass applies (data-dependent if/while ->
        jit.cond/while_loop, reference program_translator semantics), the
        original otherwise.  ProgramTranslator.enable(False) bypasses
        this entirely — the ORIGINAL runs eagerly."""
        if self._traced_fn is None:
            from . import dy2static

            try:
                self._traced_fn = dy2static.convert_function(self._fn)
            except Exception:  # noqa: BLE001 — the pass must never break
                self._traced_fn = self._fn
        return self._traced_fn

    def __get__(self, instance, owner=None):
        if instance is None:
            return self
        bound = self._bound_cache.get(id(instance))
        if bound is None:
            bound = StaticFunction(self._fn.__get__(instance, owner),
                                   self._input_spec,
                                   loop_max_trips=self._loop_max_trips)
            self._bound_cache[id(instance)] = bound
        return bound

    def _discover(self, args, kwargs):
        layers = _find_layers(self._fn)
        opts = _find_optimizers(self._fn)
        for a in list(args) + list(kwargs.values()):
            if isinstance(a, Layer):
                for l in _find_layers(a):
                    if all(l is not x for x in layers):
                        layers.append(l)
        self._layers = layers
        self._optimizers = opts

    def __call__(self, *args, **kwargs):
        # global dy2static switch (ProgramTranslator.enable(False) runs
        # the original python function eagerly, reference semantics)
        if not ProgramTranslator._enabled:
            return self._fn(*args, **kwargs)
        if self._layers is None:
            self._discover(args, kwargs)
        if self._state is None or \
                self._state_version != Layer._structure_version:
            # param/buffer handle lists are stable until SOME layer
            # mutates structurally (cheap global int compare); the
            # VALUES are read through the handles each call (read()),
            # and opt slots are re-walked in signature()/opt_slots()
            self._state = _State(self._layers, self._optimizers)
            self._state_version = Layer._structure_version
            self._mode_layers = None  # sublayer list may have changed
        state = self._state

        raw_tree = jax.tree_util.tree_map(
            lambda x: x._value if isinstance(x, Tensor) else x, (args, kwargs),
            is_leaf=lambda x: isinstance(x, Tensor))
        flat, treedef = jax.tree_util.tree_flatten(raw_tree)
        dyn_idx = [i for i, v in enumerate(flat) if _is_arrayish(v)]
        dyn_vals = [flat[i] for i in dyn_idx]
        static_flat = [None if i in dyn_idx else v for i, v in enumerate(flat)]

        # train/eval mode is part of the program (dropout identity, BN
        # statistics source), not a traced value — a .eval() flip after
        # compilation must select/build a different executable, or the
        # train-mode program keeps running silently.  The sublayer LIST
        # is cached (stable per discovery); the flags are read per call.
        if self._mode_layers is None:
            self._mode_layers = [sl for layer in self._layers
                                 for sl in layer.sublayers(
                                     include_self=True)]
        mode_key = tuple(sl.training for sl in self._mode_layers)
        # the mesh is part of the program: a distributed.MeshExecutor
        # bound here (executor.install) means the entry jits with
        # explicit per-invar shardings, and a mesh change must
        # select/build a different executable
        mesh_exec = getattr(self, "_mesh_executor", None)
        key = (_spec_key(static_flat, treedef, dyn_vals), state.signature(),
               mode_key,
               None if mesh_exec is None else mesh_exec.cache_token())
        entry = self._cache.get(key)
        if entry is None:
            in_sh = (None if mesh_exec is None
                     else mesh_exec.train_in_shardings(state, dyn_vals))
            entry = _CompiledEntry(self._trace_target(), state, treedef,
                                   static_flat, tuple(dyn_idx),
                                   in_shardings=in_sh,
                                   mesh_exec=mesh_exec)
            self._cache[key] = entry

        # host numpy (not device jnp): in a multi-controller runtime
        # (jax.distributed.initialize) a committed single-device array is
        # not a valid jit input over a multi-process mesh, while numpy
        # values are treated as replicated (same on every process)
        lrs = np.asarray([opt.get_lr() for opt in state.optimizers],
                         np.float32)
        # host-derived key data (counter XOR seed): no traced op per call
        # — and identically replicated across multi-controller processes
        rng_key = rnd.default_generator().next_key_data()
        from .dy2static import _LOOP_MAX_TRIPS

        _LOOP_MAX_TRIPS.append(self._loop_max_trips)
        try:
            if entry._param_mutated is None:
                entry.probe_trace(state, dyn_vals, lrs, rng_key)
            if entry._param_mutated is False and \
                    getattr(entry, "_out_all_arrays", False) and \
                    dispatch.is_grad_enabled():
                orig_flat = jax.tree_util.tree_flatten(
                    (args, kwargs),
                    is_leaf=lambda x: isinstance(x, Tensor))[0]
                dyn_objs = [orig_flat[i] for i in dyn_idx]
                if any(not p.stop_gradient for p in state.params) or any(
                        isinstance(o, Tensor) and not o.stop_gradient
                        for o in dyn_objs):
                    # forward-only wrap under grad recording: the
                    # reference's canonical `@to_static` ON THE MODEL
                    # with backward outside — the compiled call must be
                    # externally differentiable
                    return entry.run_diff(state, dyn_objs, dyn_vals,
                                          lrs, rng_key)
            return entry.run(state, dyn_vals, lrs, rng_key)
        finally:
            _LOOP_MAX_TRIPS.pop()

    def trace_jaxpr(self, *args, **kwargs):
        """Abstractly trace ONE call and return ``(closed_jaxpr,
        donated_mask)`` for static analysis (paddle_tpu.analysis.xray).

        Mirrors ``__call__``'s plumbing — state discovery, Tensor
        flattening, dy2static, loop bounds — but hands the entry's
        ``jax_fn`` to ``jax.make_jaxpr`` instead of executing it.  The
        flattened invars are ``state_vals ++ dyn_vals ++ lrs ++ rng_key``
        and the real call path jits with ``donate_argnums=(0,)``, so the
        mask marks exactly the state leaves as donated.  Cleanup follows
        ``probe_trace``: optimizer slots materialized under the abstract
        trace hold tracers and are deleted; live params/buffers are
        restored by ``jax_fn``'s own finally.  The python body runs once
        under tracing, so user python side effects (step counters) fire —
        same caveat as any extra trace.
        """
        if self._layers is None:
            self._discover(args, kwargs)
        if self._state is None or \
                self._state_version != Layer._structure_version:
            self._state = _State(self._layers, self._optimizers)
            self._state_version = Layer._structure_version
            self._mode_layers = None
        state = self._state

        raw_tree = jax.tree_util.tree_map(
            lambda x: x._value if isinstance(x, Tensor) else x,
            (args, kwargs), is_leaf=lambda x: isinstance(x, Tensor))
        flat, treedef = jax.tree_util.tree_flatten(raw_tree)
        dyn_idx = [i for i, v in enumerate(flat) if _is_arrayish(v)]
        dyn_vals = [flat[i] for i in dyn_idx]
        static_flat = [None if i in dyn_idx else v
                       for i, v in enumerate(flat)]
        # a fresh entry, NOT cached: this trace never lowers/compiles,
        # and a real call must still get its own trace-exactly-once entry
        entry = _CompiledEntry(self._trace_target(), state, treedef,
                               static_flat, tuple(dyn_idx))
        entry._live_state = state
        state_vals = state.read()
        lrs = np.asarray([opt.get_lr() for opt in state.optimizers],
                         np.float32)
        rng_key = rnd.default_generator().next_key_data()
        from .dy2static import _LOOP_MAX_TRIPS

        _LOOP_MAX_TRIPS.append(self._loop_max_trips)
        pre = set(entry._pre_slot_ids)
        try:
            closed = jax.make_jaxpr(entry._jax_fn)(
                state_vals, list(dyn_vals), lrs, rng_key)
        finally:
            _LOOP_MAX_TRIPS.pop()
            for s, k in list(state.opt_slots()):
                if (id(s), k) not in pre:
                    del s[k]
        n_state = len(state_vals)
        n_in = len(closed.jaxpr.invars)
        donated = tuple(i < min(n_state, n_in) for i in range(n_in))
        return closed, donated

    def compiled_programs(self):
        """The ``jax.stages.Compiled`` of every cache entry that has
        run, oldest first — what actually executes, for audits that
        read its HLO text or ``memory_analysis()``."""
        return [e._compiled for e in self._cache.values()
                if e._compiled is not None]

    # ----- parity helpers
    @property
    def code(self):
        import inspect

        return inspect.getsource(self._fn)

    def rollback(self):
        return self._fn


class _CompiledEntry:
    def __init__(self, fn, state_example, treedef, static_flat, dyn_idx,
                 in_shardings=None, mesh_exec=None):
        self._fn = fn
        self._treedef = treedef
        self._static_flat = static_flat
        self._dyn_idx = dyn_idx
        self._pre_slot_ids = [(id(s), k) for s, k in state_example.opt_slots()]
        self._new_slot_handles = []  # [(store, key)] discovered at trace time
        self._out_template = None
        # None until the first trace; False = the program leaves params
        # untouched (forward-only wrap) so external backward must work
        self._param_mutated = None
        self._nodonate = None
        self._diff_impl = None
        self._bwd_exec = None
        self._lowered = None
        self._compiled = None

        entry = self

        def jax_fn(state_vals, dyn_vals, lrs, rng_key):
            state = entry._live_state
            orig_vals = state.read()
            pre_slots = state.opt_slots()
            state.write(state_vals, slots=pre_slots)
            counter = itertools.count()

            def key_provider():
                return jax.random.fold_in(rng_key, next(counter))

            prev_provider = rnd.set_trace_key_provider(key_provider)
            prev_lrs = [opt._learning_rate for opt in state.optimizers]
            for i, opt in enumerate(state.optimizers):
                opt._learning_rate = _TracedLR(lrs[i])
            try:
                flat2 = list(entry._static_flat)
                for pos, v in zip(entry._dyn_idx, dyn_vals):
                    flat2[pos] = Tensor(v, stop_gradient=True)
                call_args, call_kwargs = jax.tree_util.tree_unflatten(
                    entry._treedef, flat2)
                with dispatch.static_trace_guard():
                    out = entry._fn(*call_args, **call_kwargs)

                post_slots = state.opt_slots()
                pre_ids = set(entry._pre_slot_ids)
                known_vals = [s[k] for s, k in post_slots
                              if (id(s), k) in pre_ids]
                new_handles = [(s, k) for s, k in post_slots
                               if (id(s), k) not in pre_ids]
                new_vals = [s[k] for s, k in new_handles]
                entry._new_slot_handles = new_handles
                n_pb = len(state.params) + len(state.buffers)
                cur = state.read()
                new_state = cur[:n_pb] + known_vals + new_vals
                if mesh_exec is not None:
                    # pin the state OUTPUTS to the planned layout: XLA's
                    # sharding propagation-to-output is otherwise free to
                    # reshard them (observed: replicated norm weights
                    # coming back fsdp-sharded), and the next call's
                    # committed args would then mismatch in_shardings
                    known_handles = [(s, k) for s, k in post_slots
                                     if (id(s), k) in pre_ids]
                    new_state = mesh_exec.constrain_state_outputs(
                        state, new_state, known_handles + new_handles)
                # identity check on tracers: a param the program never
                # touched passes through as the SAME tracer object —
                # learned here so __call__ can route forward-only wraps
                # through the externally-differentiable path
                n_p = len(state.params)
                entry._param_mutated = any(
                    c is not s for c, s in zip(cur[:n_p], state_vals[:n_p]))

                out_raw = jax.tree_util.tree_map(
                    lambda x: x._value if isinstance(x, Tensor) else x, out,
                    is_leaf=lambda x: isinstance(x, Tensor))
                entry._out_template = jax.tree_util.tree_structure(
                    out_raw, is_leaf=lambda x: x is None)
                entry._out_all_arrays = all(
                    _is_arrayish(leaf) or hasattr(leaf, "aval")
                    for leaf in jax.tree_util.tree_flatten(out_raw)[0])
            finally:
                rnd.set_trace_key_provider(prev_provider)
                for opt, prev in zip(state.optimizers, prev_lrs):
                    opt._learning_rate = prev
                # restore concrete state so tracers never leak into live objs
                state.write(orig_vals, slots=pre_slots)
            return out_raw, new_state

        # the program takes the wrapped function's name: a profiler trace
        # and the HLO then read ``jit_train_step``, not ``jit_jax_fn``
        # for every to_static program
        name = getattr(fn, "__name__", None)
        if name:
            jax_fn.__name__ = jax_fn.__qualname__ = name
        self._jax_fn = jax_fn
        if in_shardings is None:
            self._jitted = jax.jit(jax_fn, donate_argnums=(0,))
        else:
            # GSPMD execution (distributed.MeshExecutor): committed
            # per-invar layouts make this one multi-device program, and
            # donation pins the state outputs to the same layouts
            self._jitted = jax.jit(jax_fn, donate_argnums=(0,),
                                   in_shardings=in_shardings)

    def run(self, state, dyn_vals, lrs, rng_key):
        self._live_state = state
        n_known = (len(state.params) + len(state.buffers)
                   + len(self._pre_slot_ids))
        if self._compiled is None and self._lowered is not None:
            try:
                self._compiled = self._lowered.compile()
            except Exception as e:  # noqa: BLE001
                import warnings

                # the plain-jit fallback RE-TRACES the python body — a
                # documented trace-exactly-once violation (user python
                # side effects like step counters run twice), so say so
                # instead of silently desyncing (ADVICE r4)
                warnings.warn(
                    f"compiled-call build failed ({type(e).__name__}: "
                    f"{e}); falling back to plain jit, which re-traces "
                    "the function body (python side effects run again)")
                self._lowered = None  # fall back to the plain jit call
        if self._compiled is not None:
            out_raw, new_state = self._compiled(
                state.read(), list(dyn_vals), lrs, rng_key)
        else:
            out_raw, new_state = self._jitted(state.read(), dyn_vals, lrs,
                                              rng_key)
        pre_slots = [(s, k) for s, k in state.opt_slots()
                     if (id(s), k) in set(self._pre_slot_ids)]
        state.write(new_state[:n_known], slots=pre_slots)
        for (store, k), v in zip(self._new_slot_handles, new_state[n_known:]):
            store[k] = v
        return jax.tree_util.tree_map(
            lambda v: Tensor(v) if _is_arrayish(v) else v, out_raw)

    def probe_trace(self, state, dyn_vals, lrs, rng_key):
        """Abstractly trace once (no execution) so _param_mutated and the
        output template are known before choosing an execution path."""
        self._live_state = state
        pre = set(self._pre_slot_ids)
        try:
            # the SAME lowering later compiles into the standard path's
            # executable — the python body must trace exactly once per
            # entry (user code may have python-side effects, e.g.
            # gradient-merge step counters; a second trace desyncs them)
            self._lowered = self._jitted.lower(
                state.read(), list(dyn_vals), lrs, rng_key)
        except Exception:  # noqa: BLE001 — let the real call surface it
            self._param_mutated = True
        finally:
            # optimizer slots materialized during the ABSTRACT trace hold
            # tracers (nothing executed, so nothing wrote real values) —
            # delete the VALUES; _new_slot_handles is kept so run()'s
            # writeback recreates the entries from the compiled program's
            # concrete outputs
            for s, k in list(state.opt_slots()):
                if (id(s), k) not in pre:
                    del s[k]

    def _ensure_diff(self, state):
        if self._diff_impl is not None:
            return
        _register_diff_dispatch()

        jax_fn = self._jax_fn
        self._n_params = len(state.params)
        self._nodonate = jax.jit(jax_fn)

        def _flat_out(sv, dv, lrs, key):
            out_raw, _ns = jax_fn(sv, dv, lrs, key)
            return tuple(jax.tree_util.tree_flatten(out_raw)[0])

        @jax.jit
        def _bwd(pv, rest, dv, lrs, key, ct):
            # recompute-based vjp (one extra forward at backward time);
            # jitted, so the linearization compiles ONCE per signature
            _, vjp = jax.vjp(
                lambda p, d: _flat_out(list(p) + list(rest), list(d),
                                       lrs, key), tuple(pv), tuple(dv))
            return vjp(tuple(ct))

        self._bwd_exec = _bwd
        self._diff_impl = _to_static_diff_impl

    def run_diff(self, state, dyn_objs, dyn_vals, lrs, rng_key):
        """Externally-differentiable execution for programs that leave
        params untouched (the reference's canonical `@to_static` on the
        MODEL, backward outside).  The compiled forward rides the tape
        as ONE op; grads reach params and differentiable inputs via a
        cached jitted recompute-vjp.  Buffer/slot mutations (BN stats)
        still write back."""
        from ..core.dispatch import apply

        self._live_state = state
        self._ensure_diff(state)
        dyn_wrapped = [
            d if isinstance(d, Tensor) else Tensor(jnp.asarray(v),
                                                   stop_gradient=True)
            for d, v in zip(dyn_objs, dyn_vals)]
        lr_t = Tensor(jnp.asarray(lrs), stop_gradient=True)
        key_t = Tensor(jnp.asarray(rng_key), stop_gradient=True)
        _DIFF_ENTRY_STACK.append(self)
        try:
            out = apply("to_static_call", self._diff_impl,
                        list(state.params), dyn_wrapped, lr_t, key_t)
        finally:
            _DIFF_ENTRY_STACK.pop()
        out = out if isinstance(out, tuple) else (out,)
        new_state = self._diff_new_state
        n_known = (len(state.params) + len(state.buffers)
                   + len(self._pre_slot_ids))
        pre_slots = [(s, k) for s, k in state.opt_slots()
                     if (id(s), k) in set(self._pre_slot_ids)]
        # params are untouched by definition of this path: write back
        # buffers + slots only, keeping param objects bound to the tape.
        # When apply bypassed the rule (AMP cast, no-grad raw path inside
        # a vjp trace), new_state leaves may be tracers of a trace we
        # don't own — skip those writebacks rather than poison live state.
        n_p = len(state.params)
        buf_and_slots = new_state[n_p:n_known]

        def _safe(old, v):
            return old if isinstance(v, jax.core.Tracer) and not isinstance(
                old, jax.core.Tracer) else v

        for b, v in zip(state.buffers, buf_and_slots[:len(state.buffers)]):
            b._value = _safe(b._value, v)
        for (s, k), v in zip(pre_slots, buf_and_slots[len(state.buffers):]):
            s[k] = _safe(s[k], v)
        for (store, k), v in zip(self._new_slot_handles,
                                 new_state[n_known:]):
            if not isinstance(v, jax.core.Tracer):
                store[k] = v
        return jax.tree_util.tree_unflatten(self._diff_out_td, list(out))


# ---- shared dispatch for externally-differentiable compiled calls.
# ONE registry entry total (registered lazily); the active _CompiledEntry
# rides a stack around the apply() call, so entries are never pinned by
# the module-global registry and the rule scan stays O(1).
_DIFF_ENTRY_STACK: List["_CompiledEntry"] = []
_DIFF_REGISTERED = []


def _to_static_diff_impl(params, dyn, lrs, key):
    """Fallback executable for apply() paths that bypass the eager-vjp
    rule (AMP-cast dispatch, raw no-grad calls, vjp re-trace): runs the
    non-donating compiled program directly.  Under an outer jax trace it
    simply inlines."""
    entry = _DIFF_ENTRY_STACK[-1]
    n_p = entry._n_params
    sv = entry._live_state.read()
    out_raw, new_state = entry._nodonate(
        list(params) + sv[n_p:], list(dyn), lrs, key)
    entry._diff_new_state = new_state
    flat, td = jax.tree_util.tree_flatten(out_raw)
    entry._diff_out_td = td
    return tuple(flat)


def _to_static_diff_rule(vals, attrs):
    # vals: flattened [*params, *dyn, lrs_arr, key_arr] raw values
    entry = _DIFF_ENTRY_STACK[-1]
    n_p = entry._n_params
    nd = len(vals) - n_p - 2
    pv, dv = vals[:n_p], vals[n_p:n_p + nd]
    lrs_v, key_v = vals[-2], vals[-1]
    sv = entry._live_state.read()
    out_raw, new_state = entry._nodonate(
        list(pv) + sv[n_p:], list(dv), lrs_v, key_v)
    entry._diff_new_state = new_state
    flat, td = jax.tree_util.tree_flatten(out_raw)
    entry._diff_out_td = td
    rest = tuple(sv[n_p:])
    bwd = entry._bwd_exec

    def vjp_all(ct):
        ct_t = tuple(ct) if isinstance(ct, (tuple, list)) else (ct,)
        gp, gd = bwd(tuple(pv), rest, tuple(dv), lrs_v, key_v, ct_t)
        return tuple(gp) + tuple(gd) + (None, None)

    return tuple(flat), vjp_all


def _register_diff_dispatch():
    if not _DIFF_REGISTERED:
        from ..core import dispatch as _d

        _d.register_eager_vjp("to_static_call", _to_static_diff_impl,
                              _to_static_diff_rule, allow_containers=True)
        _DIFF_REGISTERED.append(True)


class _TracedLR(float):
    """float subclass carrying the traced LR; arithmetic with arrays uses the
    traced value (optimizer rules receive it as a jit argument)."""

    def __new__(cls, traced):
        obj = super().__new__(cls, float("nan"))
        obj.traced = traced
        return obj


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, loop_max_trips=None, **kwargs):
    """Decorator/wrapper compiling a function or Layer to XLA.

    ``loop_max_trips=N`` bounds tensor-condition python loops (dy2static
    while / for-range over a Tensor) so they lower to the differentiable
    bounded while (scan-of-cond) instead of forward-only XLA While —
    training scripts with data-dependent loops then work unchanged."""
    if isinstance(function, Layer):
        function.forward = StaticFunction(function.forward, input_spec,
                                          loop_max_trips=loop_max_trips)
        return function
    if function is not None:
        return StaticFunction(function, input_spec,
                              loop_max_trips=loop_max_trips)

    def deco(fn):
        return to_static(fn, input_spec, build_strategy, backend,
                         loop_max_trips=loop_max_trips, **kwargs)
    return deco


def not_to_static(fn):
    fn._not_to_static = True
    return fn


def ignore_module(modules):
    """Mark every public function of the given module(s) as not-to-static
    (reference: jit/api.py ignore_module tells the AST transcriber to skip
    third-party modules).  Here trace-based to_static executes Python
    directly, so "ignored" means: functions keep their eager semantics and
    are never rewritten — implemented by tagging them like @not_to_static
    so the dy2static AST pass and trace machinery leave them alone."""
    import types

    if not isinstance(modules, (list, tuple)):
        modules = [modules]
    for mod in modules:
        for attr in dir(mod):
            fn = getattr(mod, attr, None)
            if isinstance(fn, types.FunctionType) and \
                    getattr(fn, "__module__", None) == getattr(
                        mod, "__name__", None):
                try:
                    fn._not_to_static = True
                except (AttributeError, TypeError):
                    pass


# ------------------------------------------------------------- control flow
def _as_raw(x):
    return x._value if isinstance(x, Tensor) else x


def _wrap_tree(t):
    return jax.tree_util.tree_map(
        lambda v: Tensor(v) if _is_arrayish(v) else v, t)


def _unwrap_tree(t):
    return jax.tree_util.tree_map(
        _as_raw, t, is_leaf=lambda x: isinstance(x, Tensor))


def _collect_captured_params(fn, seen=None, depth=0):
    """Differentiable Tensors reachable from fn's closure — recursing
    into nested function cells, Layers (their parameters), and small
    containers.  These must ride as explicit tape operands or backward
    through a dispatched cond/scan silently misses them (the classic
    RNN-cell-closing-over-weights pattern)."""
    if seen is None:
        seen = {}
    if fn is None or depth > 4:
        return seen
    for cell in getattr(fn, "__closure__", None) or ():
        try:
            _collect_from_value(cell.cell_contents, seen, depth)
        except ValueError:  # empty cell
            continue
    # module-global tensors/layers the code references by NAME (a
    # module-level ``lin = nn.Linear(...)`` used inside the body is just
    # as load-bearing as a closure cell); _code_global_names scans
    # LOAD_GLOBALs of the body's (possibly nested) code objects.
    code = getattr(fn, "__code__", None)
    glob = getattr(fn, "__globals__", None)
    if code is not None and glob is not None:
        for nm in _code_global_names(code):
            v = glob.get(nm)
            if isinstance(v, (Tensor, Layer)):
                _collect_from_value(v, seen, depth)
    return seen


def _collect_from_value(v, seen, depth):
    if isinstance(v, Tensor):
        if not v.stop_gradient and id(v) not in seen:
            seen[id(v)] = v
    elif isinstance(v, Layer):
        for p in v.parameters():
            if not p.stop_gradient and id(p) not in seen:
                seen[id(p)] = p
    elif isinstance(v, (list, tuple)) and len(v) <= 64:
        for e in v:
            _collect_from_value(e, seen, depth)
    elif isinstance(v, dict) and len(v) <= 64:
        for e in v.values():
            _collect_from_value(e, seen, depth)
    elif callable(v) and (getattr(v, "__closure__", None)
                          or getattr(v, "__code__", None)):
        _collect_captured_params(v, seen, depth + 1)


@contextlib.contextmanager
def _substituted(captured, vals):
    """Temporarily rebind each captured Tensor's ``_value`` (functional
    substitution during a control-flow trace) with no grad recording —
    the dispatched outer op owns differentiation.  ONE implementation
    shared by cond/scan/while so the substitution protocol cannot
    drift between them."""
    from ..core.dispatch import no_grad_ctx

    saved = [t._value for t in captured]
    try:
        for t, v in zip(captured, vals):
            t._value = v
        with no_grad_ctx():
            yield
    finally:
        for t, s in zip(captured, saved):
            t._value = s


def _tape_cond(pred, true_fn, false_fn, operands, op_name="jit_cond"):
    """Dispatch ONE tape op whose forward is lax.cond — jax-
    differentiable, so backward reaches both the explicit operands and
    any differentiable tensors the branches capture by closure (those
    are auto-promoted to operands and functionally substituted during
    the branch trace).  Shared by jit.cond and the dy2static if-rewrite."""
    from ..core.dispatch import apply

    captured = list({**_collect_captured_params(true_fn),
                     **_collect_captured_params(false_fn)}.values())
    out_td = []

    def _fn(p, ops, cap_vals):
        def run(branch):
            def inner(packed):
                raw_ops, caps = packed
                with _substituted(captured, caps):
                    res = _unwrap_tree(branch(*_wrap_tree(raw_ops)))
                flat, td = jax.tree_util.tree_flatten(res)
                if not out_td:
                    out_td.append(td)
                return tuple(flat)
            return inner
        return jax.lax.cond(p, run(true_fn), run(false_fn),
                            (ops, tuple(cap_vals)))

    out = apply(op_name, _fn, pred, list(operands), list(captured))
    out = out if isinstance(out, tuple) else (out,)
    return jax.tree_util.tree_unflatten(out_td[0], list(out))


def cond(pred, true_fn, false_fn, *operands):
    """Functional conditional lowered to XLA Cond (reference:
    fluid/layers/control_flow.py cond).  Differentiable through the tape
    for operands AND closure-captured tensors/layer parameters."""
    return _tape_cond(pred, true_fn, false_fn, operands)


def while_loop(cond_fn, body_fn, loop_vars, maximum_trip_count=None):
    """Functional while lowered to XLA While (reference: while_loop:1167).

    Without ``maximum_trip_count``, forward-only by backend design: XLA
    While has no static trip count, so reverse mode cannot stage the
    per-iteration residuals.  The loop rides the tape as ONE op whose
    vjp RAISES — backward through it is a loud NotImplementedError
    instead of silently-zero gradients (the reference's static While IS
    differentiable via a while_grad stack, so silence here would be
    silently-wrong training math).  Captured layer weights are promoted
    to operands exactly so that backward finds the op and fails loudly
    even when no explicit loop var requires grad.

    With ``maximum_trip_count=N`` the loop lowers to a bounded
    ``lax.scan`` of length N with a predicate mask — fully reverse-
    differentiable (the TPU-native analog of the reference's
    while_grad stack, which stages residuals dynamically).  Semantics:
    the state stops updating once the predicate goes false; if the
    predicate is still true after N trips the loop TRUNCATES at N (pick
    N as a real upper bound).  Cost is N body evaluations regardless of
    the dynamic trip count."""
    from ..core.dispatch import apply

    captured = list({**_collect_captured_params(cond_fn),
                     **_collect_captured_params(body_fn)}.values())
    meta = []

    if maximum_trip_count is not None:
        n = int(maximum_trip_count)
        if n < 0:
            raise ValueError("maximum_trip_count must be >= 0")

        def _fn_bounded(loop_vals, cap_vals):
            # canonicalize so both lax.cond branches produce identical
            # avals (python-int loop vars would come back weakly typed
            # from one branch and strongly from the other)
            init = tuple(jnp.asarray(v) for v in loop_vals)

            def run_body(state):
                with _substituted(captured, cap_vals):
                    res = body_fn(*_wrap_tree(state))
                if not isinstance(res, (tuple, list)):
                    res = (res,)
                new = tuple(_unwrap_tree(tuple(res)))
                return tuple(jnp.asarray(v).astype(s.dtype)
                             for v, s in zip(new, state))

            def step(state, _):
                with _substituted(captured, cap_vals):
                    pred = _as_raw(cond_fn(*_wrap_tree(state)))
                # lax.cond, NOT a jnp.where mask: the untaken branch's
                # vjp never runs, so a body that would produce inf/NaN
                # on the frozen post-termination state (e.g. t/(n-i))
                # cannot poison gradients with 0*inf — the classic
                # where-NaN trap — and masked-out iterations skip the
                # body's FLOPs at runtime too.
                return jax.lax.cond(pred, run_body, lambda st: st,
                                    state), None

            final, _ = jax.lax.scan(step, init, None, length=n)
            flat, td = jax.tree_util.tree_flatten(final)
            if not meta:
                meta.append(td)
            return tuple(flat)

        out = apply("jit_while_bounded", _fn_bounded, list(loop_vars),
                    list(captured))
        out = out if isinstance(out, tuple) else (out,)
        return jax.tree_util.tree_unflatten(meta[0], list(out))

    @jax.custom_vjp
    def _run(loop_raw, cap_vals):
        def with_caps(fn, vs, caps):
            with _substituted(captured, caps):
                return fn(*_wrap_tree(vs))

        def run_body(st):
            res = with_caps(body_fn, st[0], st[1])
            if not isinstance(res, (tuple, list)):
                res = (res,)  # single loop var: body may return it bare
            return tuple(_unwrap_tree(tuple(res))), st[1]

        out, _ = jax.lax.while_loop(
            lambda st: _as_raw(with_caps(cond_fn, st[0], st[1])),
            run_body, (tuple(loop_raw), tuple(cap_vals)))
        return out

    def _fwd(loop_raw, cap_vals):
        return _run(loop_raw, cap_vals), None

    def _bwd(res, ct):
        raise NotImplementedError(
            "reverse-mode gradient through jit.while_loop (or a "
            "dy2static while / for-range over a Tensor bound) is not "
            "supported: XLA While has no static trip count to stage "
            "residuals over.  Use jit.while_loop(..., "
            "maximum_trip_count=N) (bounded scan, differentiable), a "
            "python-int loop bound (unrolls at trace time), jit.scan "
            "over a fixed length, or run the loop under "
            "paddle.no_grad().")

    _run.defvjp(_fwd, _bwd)

    def _fn(loop_vals, cap_vals):
        out = _run(tuple(loop_vals), tuple(cap_vals))
        flat, td = jax.tree_util.tree_flatten(out)
        if not meta:
            meta.append(td)
        return tuple(flat)

    out = apply("jit_while", _fn, list(loop_vars), list(captured))
    out = out if isinstance(out, tuple) else (out,)
    return jax.tree_util.tree_unflatten(meta[0], list(out))


def scan(f, init, xs):
    """lax.scan with Tensor wrapping; the TPU-idiomatic loop primitive.

    Dispatched through the tape (lax.scan supports reverse mode), so
    backward through a scan reaches init/xs — matching cond.  XLA While
    (jit.while_loop) remains forward-only by backend design."""
    from ..core.dispatch import apply, no_grad_ctx

    captured = list(_collect_captured_params(f).values())
    meta = []

    def _fn(init_raw, xs_raw, cap_vals):
        def body(c, x):
            with _substituted(captured, cap_vals):
                new_c, y = f(_wrap_tree(c), _wrap_tree(x))
            return _unwrap_tree(new_c), _unwrap_tree(y)

        carry, ys = jax.lax.scan(body, init_raw, xs_raw)
        cf, ctd = jax.tree_util.tree_flatten(carry)
        yf, ytd = jax.tree_util.tree_flatten(ys)
        if not meta:
            meta.append((len(cf), ctd, ytd))
        return tuple(cf) + tuple(yf)

    out = apply("jit_scan", _fn, init, xs, list(captured))
    out = out if isinstance(out, tuple) else (out,)
    n, ctd, ytd = meta[0]
    return (jax.tree_util.tree_unflatten(ctd, list(out[:n])),
            jax.tree_util.tree_unflatten(ytd, list(out[n:])))


# ------------------------------------------------------------- save / load
def save(layer, path, input_spec=None, **configs):
    """Export for serving: serialized StableHLO + weights in one artifact
    (reference: paddle.jit.save → inference program + persistables)."""
    import pickle

    if isinstance(layer, Layer):
        layer.eval()
        fn = layer.forward
        state = {k: np.asarray(v.numpy())
                 for k, v in layer.state_dict().items()}
    else:
        fn = layer
        state = {}
    if isinstance(fn, StaticFunction):
        fn = fn._trace_target()
    else:
        # the export trace needs the same dy2static pass as to_static:
        # a tensor-condition `if`/loop in forward must lower to XLA
        # Cond/While, not hit a trace-time bool conversion
        from . import dy2static

        try:
            fn = dy2static.convert_function(fn)
        except Exception:  # noqa: BLE001 — fall back to the raw fn
            pass
    if input_spec is None:
        raise ValueError("jit.save requires input_spec")

    shapes = [jax.ShapeDtypeStruct(
        tuple(d if d and d > 0 else 1 for d in spec.shape),
        to_np(spec.dtype)) for spec in input_spec]

    def pure_fn(*arg_vals):
        with dispatch.no_grad_ctx(), dispatch.static_trace_guard():
            args = [Tensor(v) for v in arg_vals]
            out = fn(*args)
        return jax.tree_util.tree_map(
            lambda x: x._value if isinstance(x, Tensor) else x, out,
            is_leaf=lambda x: isinstance(x, Tensor))

    exported = jax.export.export(jax.jit(pure_fn))(*shapes)
    blob = {
        "stablehlo": exported.serialize(),
        "state": state,
        "input_spec": [(list(s.shape), str(s.dtype)) for s in shapes],
    }
    fname = path if path.endswith(".pdmodel") else path + ".pdmodel"
    with open(fname, "wb") as f:
        pickle.dump(blob, f, protocol=4)
    return fname


class LoadedFunction:
    """Deserialized serving artifact; __call__ runs the compiled program."""

    def __init__(self, exported, state):
        self._exported = exported
        self._state = state

    def __call__(self, *args):
        raw = [a._value if isinstance(a, Tensor) else jnp.asarray(a)
               for a in args]
        out = self._exported.call(*raw)
        return jax.tree_util.tree_map(
            lambda v: Tensor(v) if _is_arrayish(v) else v, out)

    def eval(self):
        return self

    def state_dict(self):
        return self._state


def load(path, **configs):
    import pickle

    fname = path if path.endswith(".pdmodel") else path + ".pdmodel"
    with open(fname, "rb") as f:
        blob = pickle.load(f)
    exported = jax.export.deserialize(blob["stablehlo"])
    return LoadedFunction(exported, blob["state"])


# ---------------------------------------------------------------------------
# reference-compat surface (python/paddle/fluid/dygraph/jit.py,
# dygraph_to_static/program_translator.py)
# ---------------------------------------------------------------------------

declarative = to_static  # the reference's older decorator name


class ProgramTranslator:
    """Singleton toggling dy2static globally (reference:
    program_translator.py ProgramTranslator.get_instance().enable(False)).
    Here 'static conversion' is whole-step XLA compilation: disabling it
    makes to_static-wrapped functions run eagerly."""

    _instance = None
    _enabled = True

    @classmethod
    def get_instance(cls):
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def enable(self, enable_to_static: bool):
        ProgramTranslator._enabled = bool(enable_to_static)

    @property
    def enable_to_static(self):
        return ProgramTranslator._enabled


def enable_to_static(flag: bool):
    ProgramTranslator.get_instance().enable(flag)


def set_verbosity(level=0, also_to_stdout=False):
    """Dy2static logging verbosity (reference: logging_utils.set_verbosity).
    Maps onto the jit logger level."""
    import logging

    logging.getLogger("paddle_tpu.jit").setLevel(
        logging.DEBUG if level > 0 else logging.WARNING)
    return level


def set_code_level(level=100, also_to_stdout=False):
    """Parity shim: the reference prints transformed AST at this level;
    we have no AST transform stage (tracing does the conversion), so this
    records the setting only."""
    set_verbosity(1 if level else 0)
    return level


class TracedLayer:
    """Trace-and-replay wrapper (reference: fluid/dygraph/jit.py
    TracedLayer over program_desc_tracing): trace builds the compiled
    callable; save_inference_model exports it."""

    def __init__(self, layer, static_fn, example_args):
        self._layer = layer
        self._fn = static_fn
        self._example_args = example_args

    @staticmethod
    def trace(layer, inputs):
        fn = to_static(lambda *a: layer(*a))
        outs = fn(*inputs)
        return outs, TracedLayer(layer, fn, inputs)

    def __call__(self, *args):
        return self._fn(*args)

    def save_inference_model(self, path, feed=None, fetch=None, **kwargs):
        save(self._layer, path, input_spec=list(self._example_args))
        return path


# reference name for what jit.load returns (fluid/dygraph/io.py
# TranslatedLayer); LoadedFunction is the implementation
TranslatedLayer = LoadedFunction
