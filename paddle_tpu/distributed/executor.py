"""Runtime SPMD mesh execution — the runtime half of the shard plan.

``analysis/shardplan.py`` (PR 7) de-risked mesh sharding *statically*:
it propagates the frozen llama ``SpecLayout`` through the traced
train/decode/prefill jaxprs on an abstract mesh and prices every
implied collective.  This module executes those same steps as one
GSPMD program per step over a real ``jax.sharding.Mesh``:

- ``MeshExecutor({"data": 2, "fsdp": 2, "tp": 2})`` builds the mesh —
  from real TPU devices, or on CPU from forced host devices
  (``XLA_FLAGS=--xla_force_host_platform_device_count=8``) so tier-1
  covers every code path.  A host with fewer devices than the axes
  need is an error: a sharded run that quietly became a one-device run
  would measure, and test, something else.
- ``install(model)`` lays out params, optimizer slots (inheriting each
  param's spec, same id-matching as shardplan), batch, and RNG with
  ``NamedSharding``s and arranges for the hapi train step to be jitted
  with explicit in_shardings + donation (donation pins the state
  *outputs* to the same layout, so steady-state steps never reshard).
- ``install_serving(model, pool)`` does the same for the serving
  engine: weights sharded in place (the decode/prefill steps capture
  them as committed jit constants) and the paged KV pool laid out
  ``PS(None, None, "tp", None)``.
- ``reconcile_train`` / ``reconcile_serving`` cross-check the COMPILED
  programs against the static ``PlanReport`` — collective footprint,
  per-device memory, and realized output shard shapes — surfacing any
  divergence as diagnostic **S209** (runtime-vs-plan mismatch).  Zero
  S209s means the bytes on the wire are the bytes the plan priced.
"""
from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..core.tensor import Tensor
from .sharding import SpecLayout, get_sharding_spec

__all__ = [
    "MeshExecutor",
    "as_executor",
    "current_executor",
    "active_mesh",
    "active_mesh_axes",
    "default_shardplan_mesh",
]

S209 = "S209"

# the process-wide executor registry: sharding helpers
# (distributed/sharding.py) and tools/lint_tpu.py --shardplan fall back
# to the registered executor's mesh when no mesh is passed explicitly
_ACTIVE: Optional["MeshExecutor"] = None


def current_executor() -> Optional["MeshExecutor"]:
    return _ACTIVE


def active_mesh() -> Optional[Mesh]:
    return _ACTIVE.mesh if _ACTIVE is not None else None


def active_mesh_axes() -> Optional[Dict[str, int]]:
    return dict(_ACTIVE.axes) if _ACTIVE is not None else None


def default_shardplan_mesh() -> Optional[Dict[str, int]]:
    """The registered executor's axes, for CI audits of the mesh
    actually in use (``lint_tpu.py --shardplan`` default)."""
    return active_mesh_axes()


def as_executor(mesh) -> "MeshExecutor":
    """Coerce an ``{axis: size}`` dict / ``jax.sharding.Mesh`` /
    ``MeshExecutor`` into a ``MeshExecutor``."""
    if isinstance(mesh, MeshExecutor):
        return mesh
    if isinstance(mesh, Mesh):
        return MeshExecutor(dict(mesh.shape),
                            devices=list(mesh.devices.flat))
    if isinstance(mesh, dict):
        return MeshExecutor(mesh)
    raise TypeError(
        f"mesh must be an axis dict, jax.sharding.Mesh, or MeshExecutor, "
        f"got {type(mesh).__name__}")


def _entry_axes(entry) -> Tuple[str, ...]:
    if entry is None:
        return ()
    if isinstance(entry, (tuple, list)):
        return tuple(entry)
    return (entry,)


_COLLECTIVE_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")


def _hlo_collective_counts(hlo_text: str) -> Dict[str, int]:
    """Count collective ops in optimized HLO text (op applications only:
    the op name immediately followed by '(' — instruction *names* carry
    a '.N' suffix and never match)."""
    counts: Dict[str, int] = {}
    for m in _COLLECTIVE_RE.finditer(hlo_text):
        kind = m.group(1).replace("-", "_")
        counts[kind] = counts.get(kind, 0) + 1
    return counts


class MeshExecutor:
    """Lays out state on a named device mesh and runs each registered
    step as one GSPMD program, validated against the static shard plan.

    Parameters
    ----------
    axes: ``{axis_name: size}`` in mesh-major order, e.g.
        ``{"data": 2, "fsdp": 2, "tp": 2}``.
    layout: the ``SpecLayout`` mapping parameter roles to
        ``PartitionSpec``s (default: the canonical llama layout).
    devices: explicit device list (default ``jax.devices()``).
    register: make this the process-wide executor that sharding
        helpers and ``--shardplan`` fall back to.
    """

    def __init__(self, axes: Dict[str, int], *, layout: SpecLayout = None,
                 devices: Sequence[Any] = None, register: bool = True,
                 topology=None):
        names = list(axes)
        sizes = [int(axes[k]) for k in names]
        if not names or any(s < 1 for s in sizes):
            raise ValueError(f"invalid mesh axes {axes!r}")
        devs = list(devices) if devices is not None else list(jax.devices())
        need = int(np.prod(sizes))
        if need > len(devs):
            hint = ""
            if devs and devs[0].platform == "cpu":
                hint = (" (set XLA_FLAGS=--xla_force_host_platform_"
                        "device_count=N to emulate an N-device host)")
            raise ValueError(
                f"mesh {dict(zip(names, sizes))} needs {need} devices but "
                f"only {len(devs)} are visible{hint}")
        self.mesh = Mesh(
            np.asarray(devs[:need]).reshape(sizes), tuple(names))
        self.axes: Dict[str, int] = dict(zip(names, sizes))
        # true when the mesh's devices span >1 process (the bootstrap's
        # multi-controller runtime): host values then commit via
        # make_array_from_callback and S209 audits aggregate per-process
        self.multiprocess = len(
            {getattr(d, "process_index", 0) for d in self.mesh.devices.flat}
        ) > 1
        self.layout = layout if layout is not None else SpecLayout()
        # analysis.Topology: makes every shard plan this executor
        # requests price host-spanning collectives at DCN rates; the
        # reconcile_* entry points then refuse to bless a single-host
        # runtime against a multi-host-priced plan
        self.topology = topology
        self.reports: Dict[str, Tuple[Any, List[Any]]] = {}
        self._replicated = NamedSharding(self.mesh, PartitionSpec())
        if register:
            global _ACTIVE
            _ACTIVE = self
        self._export_gauges()

    # ----- layout primitives -------------------------------------------
    def clean_spec(self, spec, shape=None) -> PartitionSpec:
        """Restrict a PartitionSpec to this mesh: drop entries naming
        absent axes and entries whose axis product does not divide the
        dim (mirrors shardplan's ``_drop_indivisible``)."""
        entries = list(spec) if spec is not None else []
        out: List[Any] = []
        for dim, entry in enumerate(entries):
            axes = _entry_axes(entry)
            if not axes or any(a not in self.mesh.shape for a in axes):
                out.append(None)
                continue
            n = 1
            for a in axes:
                n *= int(self.mesh.shape[a])
            if shape is not None and (
                    dim >= len(shape) or int(shape[dim]) % n != 0):
                out.append(None)
                continue
            out.append(tuple(axes) if len(axes) > 1 else axes[0])
        if shape is not None:
            out = out[:len(shape)]
        while out and out[-1] is None:
            out.pop()
        return PartitionSpec(*out)

    def sharding(self, spec=None, shape=None) -> NamedSharding:
        if spec is None:
            return self._replicated
        return NamedSharding(self.mesh, self.clean_spec(spec, shape))

    @property
    def replicated(self) -> NamedSharding:
        return self._replicated

    def shard_shape(self, shape, spec) -> Tuple[int, ...]:
        """Per-device shard shape of ``shape`` under ``spec``."""
        spec = self.clean_spec(spec, shape)
        entries = list(spec) + [None] * (len(shape) - len(list(spec)))
        out = []
        for dim, entry in zip(shape, entries):
            n = 1
            for a in _entry_axes(entry):
                n *= int(self.mesh.shape[a])
            out.append(int(dim) // n)
        return tuple(out)

    def put(self, value, spec=None, shape=None):
        """Commit an array (or Tensor ``_value``) onto the mesh.  Under
        tracing, apply a sharding constraint instead.  When the mesh
        spans processes, every process passes the same GLOBAL host value
        and receives its addressable slice of the distributed array."""
        if shape is None:
            shape = tuple(np.shape(value))
        sh = self.sharding(spec, shape)
        if hasattr(value, "aval") and not hasattr(value,
                                                  "addressable_shards"):
            return jax.lax.with_sharding_constraint(value, sh)
        if self.multiprocess and not hasattr(value, "addressable_shards"):
            host = np.asarray(value)
            return jax.make_array_from_callback(
                tuple(shape), sh, lambda idx: host[idx])
        return jax.device_put(value, sh)

    def fetch(self, value) -> np.ndarray:
        """Host numpy view of a step output under any topology:
        fully-addressable (single-process) arrays read directly; a
        multi-process array reads via its local shards when replicated,
        else through an allgather — so callers never trip the
        'non-addressable array' fetch guard."""
        if isinstance(value, Tensor):
            value = value._value
        if getattr(value, "is_fully_addressable", True) or \
                getattr(value, "is_fully_replicated", False):
            return np.asarray(value)
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(
            value, tiled=True))

    # ----- state layout ------------------------------------------------
    def shard_params(self, layer) -> int:
        """Lay out every parameter per its role spec (buffers stay
        replicated) and stamp ``_sharding_spec`` so optimizer-slot
        creation and the jit in_shardings can inherit it."""
        n = 0
        for name, p in layer.named_parameters():
            shape = tuple(np.shape(p._value))
            spec = self.clean_spec(self.layout.param_spec(name), shape)
            p._value = self.put(p._value, spec, shape)
            p._sharding_spec = spec
            n += 1
        for _, b in layer.named_buffers():
            b._value = self.put(b._value, PartitionSpec())
        return n

    def _slot_sharding(self, arr, param) -> NamedSharding:
        """A slot inherits its param's spec iff shapes match (same
        id-matching rule as shardplan); scalars etc. stay replicated."""
        shape = tuple(np.shape(arr))
        if param is not None and shape == tuple(param.shape):
            spec = get_sharding_spec(param)
            if spec is not None:
                return self.sharding(spec, shape)
        return self._replicated

    def install_optimizer(self, opt) -> None:
        """Hook ``_add_accumulator`` so slots materialize directly on
        their param's layout, and pin any existing slots."""
        if getattr(opt, "_mesh_executor", None) is self:
            return
        opt._mesh_executor = self
        ex = self
        orig_add = opt._add_accumulator

        def _add_accumulator(name, param, **kw):
            arr = orig_add(name, param, **kw)
            sh = ex._slot_sharding(arr, param)
            try:
                if hasattr(arr, "aval") and not hasattr(
                        arr, "addressable_shards"):
                    arr = jax.lax.with_sharding_constraint(arr, sh)
                else:
                    arr = jax.device_put(arr, sh)
                opt._accumulators[name][id(param)] = arr
            except Exception:  # noqa: BLE001 — layout is best-effort
                pass
            return arr

        opt._add_accumulator = _add_accumulator
        self.reshard_optimizer(opt)

    def reshard_optimizer(self, opt) -> None:
        params = {}
        for entry in (getattr(opt, "_parameter_list", None) or ()):
            group = (entry.get("params", []) if isinstance(entry, dict)
                     else [entry])
            for p in group:
                if isinstance(p, Tensor):
                    params[id(p)] = p
        for name, store in getattr(opt, "_accumulators", {}).items():
            for pid, arr in list(store.items()):
                if hasattr(arr, "aval") and not hasattr(
                        arr, "addressable_shards"):
                    continue  # mid-trace slot: leave it to the program
                store[pid] = jax.device_put(
                    arr, self._slot_sharding(arr, params.get(pid)))

    def install(self, model) -> "MeshExecutor":
        """Wire a prepared ``hapi.Model`` for mesh execution: shard its
        params and slots, and bind this executor to the compiled
        train/eval steps so they jit with explicit in_shardings."""
        net = getattr(model, "network", model)
        self.shard_params(net)
        opt = getattr(model, "_optimizer", None)
        if opt is not None:
            self.install_optimizer(opt)
        for attr in ("_train_step_fn", "_eval_step_fn"):
            fn = getattr(model, attr, None)
            if fn is None:
                continue
            sfn = getattr(fn, "_fn", fn)  # unwrap compile_tracker
            if hasattr(sfn, "_cache"):
                sfn._mesh_executor = self
        model._mesh_executor = self
        net._mesh_executor = self
        return self

    def reshard(self, network, optimizer=None) -> None:
        """Re-lay-out after a host-side state load (checkpoint restore
        rebinds ``_value`` to host arrays)."""
        self.shard_params(network)
        if optimizer is not None:
            self.reshard_optimizer(optimizer)

    # ----- jit integration ---------------------------------------------
    def cache_token(self):
        """Part of the StaticFunction cache key: a mesh change must
        select/build a different executable."""
        return (tuple(self.axes.items()), id(self.mesh))

    def train_in_shardings(self, state, dyn_vals):
        """Explicit in_shardings for the hapi step's flattened invars
        ``(state_vals, dyn_vals, lrs, rng_key)``: params by role spec,
        buffers replicated, slots inheriting their param (id-matched),
        batch leaves on the batch spec, lr/rng replicated.  With
        ``donate_argnums=(0,)`` XLA pins the state *outputs* to the same
        layout — steady-state steps never reshard."""
        state_sh: List[NamedSharding] = []
        for p in state.params:
            state_sh.append(self.sharding(
                get_sharding_spec(p), tuple(np.shape(p._value))))
        for _b in state.buffers:
            state_sh.append(self._replicated)
        by_id = {id(p): p for p in state.params}
        for store, key in state.opt_slots():
            state_sh.append(self._slot_sharding(store[key], by_id.get(key)))
        batch = self.layout.batch_spec()
        dyn_sh = [self.sharding(batch, tuple(np.shape(v)))
                  for v in dyn_vals]
        return (state_sh, dyn_sh, self._replicated, self._replicated)

    def constrain_state_outputs(self, state, new_state, slot_handles):
        """Pin a traced step's state outputs to the planned layout
        (params by role spec, buffers replicated, slots inheriting their
        param).  Called inside ``jit.to_static``'s traced body: without
        it XLA's propagation-to-output may reshard state between steps
        and the next call's committed args mismatch in_shardings."""
        n_p, n_b = len(state.params), len(state.buffers)
        by_id = {id(p): p for p in state.params}
        out = list(new_state)
        for i, p in enumerate(state.params):
            sh = self.sharding(get_sharding_spec(p),
                               tuple(np.shape(out[i])))
            out[i] = jax.lax.with_sharding_constraint(out[i], sh)
        for i in range(n_p, n_p + n_b):
            out[i] = jax.lax.with_sharding_constraint(
                out[i], self._replicated)
        for j, (_store, key) in enumerate(slot_handles):
            i = n_p + n_b + j
            if i >= len(out):
                break
            sh = self._slot_sharding(out[i], by_id.get(key))
            out[i] = jax.lax.with_sharding_constraint(out[i], sh)
        return out

    def shard_batch(self, values):
        """Commit host batch leaves onto the batch spec (matching the
        step's in_shardings, so dispatch never reshards)."""
        spec = self.layout.batch_spec()
        out = []
        for v in values:
            if isinstance(v, Tensor):
                v._value = self.put(v._value, spec)
                out.append(v)
            elif v is not None and hasattr(v, "shape"):
                out.append(self.put(v, spec))
            else:
                out.append(v)
        return out

    # ----- serving -----------------------------------------------------
    def kv_pool_spec(self) -> PartitionSpec:
        # [num_blocks, block_size, kv_heads, head_dim] — heads on tp
        return PartitionSpec(None, None, self.layout.tp_axis, None)

    def static_kv_spec(self) -> PartitionSpec:
        """Sequential ``generate()`` StaticKVCache layout,
        [batch, max_len, kv_heads, head_dim] — kv heads on tp, matching
        the paged pool (``kv_pool_spec``) so the one-shot path stops
        replicating a full max_len cache per chip."""
        return PartitionSpec(None, None, self.layout.tp_axis, None)

    def shard_kv_layers(self, layers):
        spec = self.kv_pool_spec()
        # Quantized pools carry (k, v, k_scale, v_scale); the per-row
        # scale sidecars [num_blocks, block_size] have no kv-head axis
        # to shard, so they replicate.
        scale_spec = PartitionSpec(None, None)
        out = []
        for entry in layers:
            k, v = entry[0], entry[1]
            sharded = (self.put(k, spec), self.put(v, spec))
            if len(entry) == 4:
                sharded += (self.put(entry[2], scale_spec),
                            self.put(entry[3], scale_spec))
            out.append(sharded)
        return out

    def install_serving(self, model, pool) -> "MeshExecutor":
        """Shard the serving model + paged KV pool.  Must run BEFORE the
        decode/prefill step makers: the steps capture the weights as jit
        constants, so rebinding ``_value`` here is what makes the
        compiled programs SPMD."""
        self.shard_params(model)
        pool.layers = self.shard_kv_layers(pool.layers)
        model._mesh_executor = self
        return self

    # ----- observability -----------------------------------------------
    def _export_gauges(self) -> None:
        from .. import observability

        if not observability.enabled():
            return
        reg = observability.get_registry()
        reg.gauge("mesh_num_devices",
                  "devices in the executor's mesh").set(int(self.mesh.size))
        g = reg.gauge("mesh_axis_sizes",
                      "per-axis size of the executor's mesh")
        for ax, sz in self.axes.items():
            g.set(int(sz), axis=ax)
        reg.gauge("mesh_process_span",
                  "distinct processes owning this mesh's devices").set(
            len({getattr(d, "process_index", 0)
                 for d in self.mesh.devices.flat}))

    # ----- S209 reconciliation -----------------------------------------
    def _plan_request(self):
        from ..analysis import shardplan as _shardplan

        return _shardplan.PlanRequest(mesh=dict(self.axes),
                                      layout=self.layout,
                                      raise_on_error=False,
                                      topology=self.topology)

    def _check_plan_topology(self, plan) -> None:
        """A plan priced for a multi-host Topology cannot be reconciled
        against a single-host runtime: the DCN phases it prices do not
        exist on this mesh, so S209 'agreement' would be meaningless.
        Raise instead of silently blessing the wrong fleet shape."""
        topo = getattr(plan, "topology", None)
        if topo is None or int(topo.hosts) <= 1:
            return
        procs = jax.process_count()
        if procs < int(topo.hosts):
            raise RuntimeError(
                f"shard plan was priced for a {topo.hosts}-host topology "
                f"({topo.hosts} × {topo.chips_per_host_count} chips) but "
                f"this runtime spans {procs} process(es) over "
                f"{self.mesh.size} device(s) — the DCN collective phases "
                "the plan prices cannot exist on a single-host mesh; "
                f"launch under jax.distributed with {topo.hosts} "
                "processes, or drop `topology` from the MeshExecutor / "
                "PlanRequest to reconcile a single-host plan")

    def _reconcile_compiled(self, plan, compiled, *, name,
                            trailing_out_expect=None):
        """Compare one compiled program against its static PlanReport.
        Returns S209 diagnostics; an empty list means reconciled."""
        from ..analysis.verifier import Diagnostic, ERROR, WARNING

        diags: List[Any] = []
        hlo = ""
        try:
            hlo = compiled.as_text()
        except Exception:  # noqa: BLE001 — backend may not expose HLO
            pass
        if hlo and self.mesh.size > 1:
            counts = _hlo_collective_counts(hlo)
            n_run = sum(counts.values())
            if plan.comm_bytes > 0 and n_run == 0:
                diags.append(Diagnostic(
                    S209, ERROR,
                    f"static plan prices {len(plan.collectives)} "
                    f"collective(s) ({plan.comm_bytes / 2**10:.1f} KiB on "
                    "the wire) but the compiled HLO contains none — the "
                    "step is running single-device math; the input "
                    "shardings did not take", name))
            elif plan.comm_bytes == 0 and n_run > 0:
                diags.append(Diagnostic(
                    S209, WARNING,
                    f"compiled HLO contains {n_run} collective op(s) "
                    f"({counts}) where the plan prices zero bytes — the "
                    "runtime communicates off-plan", name))
        try:
            ma = compiled.memory_analysis()
            run_bytes = int(ma.argument_size_in_bytes
                            + ma.output_size_in_bytes
                            + ma.temp_size_in_bytes)
        except Exception:  # noqa: BLE001 — Unimplemented on some backends
            run_bytes = None
        if run_bytes is not None and plan.per_chip_peak_hbm_bytes > 0:
            # generous bound: the plan's peak is LIVE bytes; the compiled
            # footprint counts whole buffers — only a multiple signals a
            # layout that silently replicated what the plan sharded
            budget = 4 * int(plan.per_chip_peak_hbm_bytes) + (64 << 20)
            if run_bytes > budget:
                diags.append(Diagnostic(
                    S209, WARNING,
                    f"compiled per-device footprint {run_bytes / 2**20:.1f}"
                    f" MiB exceeds 4x the planned per-chip peak "
                    f"({plan.per_chip_peak_hbm_bytes / 2**20:.1f} MiB) + "
                    "64 MiB slack — state may be replicated instead of "
                    "sharded", name))
        if trailing_out_expect:
            try:
                outs = jax.tree_util.tree_leaves(compiled.output_shardings)
            except Exception:  # noqa: BLE001
                outs = []
            n = len(trailing_out_expect)
            tail = outs[-n:] if len(outs) >= n else []
            for (label, shape, spec), sh in zip(trailing_out_expect, tail):
                want = self.shard_shape(shape, spec)
                try:
                    got = tuple(sh.shard_shape(tuple(shape)))
                except Exception:  # noqa: BLE001 — opaque sharding repr
                    continue
                if got != want:
                    diags.append(Diagnostic(
                        S209, ERROR,
                        f"{label}: compiled output shard {got} != planned "
                        f"{want} under spec {spec} — the realized layout "
                        "diverges from the shard plan", name))
        return diags

    def reconcile_train(self, model, inputs, labels):
        """Cross-check the compiled hapi train step against the static
        plan.  Needs at least one executed train batch (the compiled
        steady-state entry is what gets audited).  Returns
        ``(PlanReport, [S209 diagnostics])``."""
        plan = model.shardplan(inputs, labels, request=self._plan_request())
        self._check_plan_topology(plan)
        fn = model._train_step_fn
        sfn = getattr(fn, "_fn", fn)
        programs = sfn.compiled_programs()
        if not programs:
            raise RuntimeError(
                "reconcile_train needs a compiled train step — run at "
                "least one train batch first")
        compiled = programs[-1]
        state = sfn._state
        names: Dict[int, str] = {}
        for layer in (sfn._layers or ()):
            for nm, p in layer.named_parameters():
                names.setdefault(id(p), nm)
        by_id = {id(p): p for p in state.params}
        expect: List[Tuple[str, Tuple[int, ...], PartitionSpec]] = []
        for p in state.params:
            nm = names.get(id(p), "param")
            shape = tuple(np.shape(p._value))
            expect.append(
                (nm, shape,
                 self.clean_spec(self.layout.param_spec(nm), shape)))
        for b in state.buffers:
            expect.append(("buffer", tuple(np.shape(b._value)),
                           PartitionSpec()))
        for store, key in state.opt_slots():
            arr = store[key]
            shape = tuple(np.shape(arr))
            p = by_id.get(key)
            spec = PartitionSpec()
            if p is not None and shape == tuple(p.shape):
                spec = self.clean_spec(
                    self.layout.param_spec(names.get(id(p), "param")),
                    shape)
            expect.append((f"slot[{names.get(key, 'global')}]", shape,
                           spec))
        diags = self._reconcile_compiled(
            plan, compiled, name="hapi::train_step",
            trailing_out_expect=expect)
        diags = self._aggregate_process_diags(
            "hapi::train_step", compiled, diags)
        self.reports["hapi::train_step"] = (plan, diags)
        return plan, diags

    def _aggregate_process_diags(self, name, compiled, diags):
        """S209 across the process boundary: every process audits its
        OWN compiled program; process 0's aggregation is an allgather of
        each process's (diag count, collective-footprint fingerprint).
        In a healthy SPMD fleet the rows are identical — a divergent row
        means some host compiled different collectives than its peers
        (skew in code, flags, or device slices), which no single-process
        audit can see."""
        if not self.multiprocess or jax.process_count() <= 1:
            return diags
        import json as _json
        import zlib

        from jax.experimental import multihost_utils

        from ..analysis.verifier import Diagnostic, ERROR

        hlo = ""
        try:
            hlo = compiled.as_text()
        except Exception:  # noqa: BLE001
            pass
        counts = _hlo_collective_counts(hlo) if hlo else {}
        fp = zlib.crc32(_json.dumps(sorted(counts.items())).encode()
                        ) & 0x7FFFFFFF
        row = np.array([len(diags), fp], dtype=np.int32)
        rows = np.asarray(multihost_utils.process_allgather(row))
        if not bool((rows == rows[0]).all()):
            # identical on every process (allgather), so the fleet
            # agrees on the verdict even though process 0 reports it
            diags.append(Diagnostic(
                S209, ERROR,
                f"processes disagree on the compiled step: per-process "
                f"(n_diags, collective_fingerprint) rows {rows.tolist()} "
                "— some host is running a divergent program", name))
        return diags

    def _serving_sds(self, arg, spec):
        """Mirror shardplan's spec broadcasting over container args and
        attach shardings to the abstract ShapeDtypeStructs."""
        if isinstance(arg, (list, tuple)):
            nested = isinstance(spec, (list, tuple)) and not isinstance(
                spec, PartitionSpec)
            seq = [self._serving_sds(a, spec[i] if nested else spec)
                   for i, a in enumerate(arg)]
            return tuple(seq) if isinstance(arg, tuple) else seq
        shape = tuple(arg.shape)
        return jax.ShapeDtypeStruct(
            shape, arg.dtype, sharding=self.sharding(spec, shape))

    def reconcile_serving(self, engine):
        """Cross-check the serving decode + prefill steps.  AOT-compiles
        each step from sharded abstract args (bypassing the retrace
        guard, so compile counters are untouched) and reconciles against
        its PlanReport.  Returns ``{step_name: (plan, diags)}``."""
        from ..analysis import shardplan as _shardplan
        from ..analysis import xray as _xray

        cfg = engine.config
        model = engine.model
        decode_args, prefill_args = _xray._serving_abstract_args(
            model, batch=cfg.max_batch_size, num_blocks=cfg.num_blocks,
            block_size=cfg.block_size,
            max_blocks_per_seq=engine.max_blocks_per_seq,
            chunk_tokens=engine.chunk_tokens)
        decode_specs, prefill_specs = _shardplan._serving_arg_specs(
            model, self.layout, decode_args, prefill_args)
        req = self._plan_request()
        out: Dict[str, Tuple[Any, List[Any]]] = {}
        for name, step, args, specs, data_leaves in (
                ("serving::decode_step", engine._decode_step,
                 decode_args, decode_specs, (("tokens", 0),)),
                ("serving::prefill_step", engine._prefill_step,
                 prefill_args, prefill_specs, (("chunk_ids", 0),))):
            plan = _shardplan.plan_step(
                step, args, model=model, arg_specs=specs, request=req,
                name=name, data_input_leaves=data_leaves,
                step_kind=("paged_decode" if "decode" in name
                           else "chunked_prefill"))
            self._check_plan_topology(plan)
            fn = step
            if hasattr(fn, "_fn") and hasattr(fn, "compiles"):
                fn = fn._fn
            sds = [self._serving_sds(a, s) for a, s in zip(args, specs)]
            compiled = fn.lower(*sds).compile()
            # both steps return (arrays, [(k, v) per layer]) — the pool
            # leaves are the trailing outputs and must come back on the
            # pool spec, or every decode step pays a reshard
            pool_spec = self.kv_pool_spec()
            expect = []
            for i, (k, v) in enumerate(args[1]):
                for tag, a in (("k", k), ("v", v)):
                    shape = tuple(a.shape)
                    expect.append((f"kv_pool[{i}].{tag}", shape,
                                   self.clean_spec(pool_spec, shape)))
            diags = self._reconcile_compiled(
                plan, compiled, name=name, trailing_out_expect=expect)
            self.reports[name] = (plan, diags)
            out[name] = (plan, diags)
        return out

    # ----- lifecycle ---------------------------------------------------
    def close(self) -> None:
        global _ACTIVE
        if _ACTIVE is self:
            _ACTIVE = None

    def __enter__(self) -> "MeshExecutor":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __repr__(self) -> str:
        return f"MeshExecutor({self.axes}, devices={self.mesh.size})"
