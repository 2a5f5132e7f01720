"""Collective communication API.

Reference surface: python/paddle/distributed/collective.py (all_reduce:592,
all_gather:814, alltoall:1738, send:1840, recv:1903, new_group:325) backed by
ProcessGroupNCCL.  TPU-native semantics:

- Inside a shard_map/SPMD trace (a mesh axis name is in scope) each call
  lowers to the XLA collective (psum / all_gather / all_to_all / ppermute)
  over ICI — this is the performance path the compiler schedules.
- Eagerly in the single-controller model there is one process that owns all
  chips: cross-"rank" collectives over a group of size 1 are identity, and
  send/recv have no peer — they raise, directing users to the SPMD path.
"""
from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp

from ..core.dispatch import apply
from ..core.tensor import Tensor
from .env import get_rank, get_world_size
from .mesh import _AxisGroup, get_mesh


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


Group = _AxisGroup

_GROUPS = {}


def _axis_in_scope(axis_name) -> bool:
    """True when called under shard_map with this axis bound."""
    if axis_name is None:
        return False
    try:
        jax.lax.axis_index(axis_name)
        return True
    except (NameError, Exception):
        return False


def new_group(ranks=None, backend=None, timeout=None):
    """Create a group.  In the SPMD model a group is a mesh-axis view; a
    ranks list matching a whole axis maps onto it, anything else gets a
    trivial group (single-controller: every collective is compiled)."""
    mesh = get_mesh()
    nranks = len(ranks) if ranks else get_world_size()
    axis = None
    if mesh is not None:
        for name, size in mesh.shape.items():
            if size == nranks:
                axis = name
                break
    g = _AxisGroup(axis, nranks, 0, ranks or range(nranks))
    _GROUPS[g.id] = g
    return g


def get_group(gid=0):
    return _GROUPS.get(gid)


def _group_axis(group):
    if group is None:
        mesh = get_mesh()
        if mesh is not None and len(mesh.shape) == 1:
            return list(mesh.shape)[0]
        return None
    return group.axis_name


def _world_mesh():
    """1-device-per-process mesh for eager cross-process collectives.

    Using one device per process (the first of each) keeps the global
    array's leading dim == process_count divisible regardless of how many
    chips each host owns; every process still participates in the compiled
    collective, so the reduction is correct on multi-chip hosts too."""
    import numpy as np
    from jax.sharding import Mesh

    per_process = {}
    for d in jax.devices():
        per_process.setdefault(d.process_index, d)
    devs = [per_process[p] for p in sorted(per_process)]
    return Mesh(np.asarray(devs), ("world",))


_CROSS_FNS = {}


def _cross_process_all_reduce(value, op):
    """Eager all-reduce across OS processes: every process contributes its
    local value to one compiled collective over the global mesh (the
    multi-controller analog of the reference ProcessGroup AllReduce task,
    ProcessGroup.h:53).  All processes must call this collectively."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = jax.process_count()
    mesh = _world_mesh()
    dev = jax.local_devices()[0]
    sharding = NamedSharding(mesh, P("world"))
    garr = jax.make_array_from_single_device_arrays(
        (n,) + value.shape, sharding,
        [jax.device_put(value[None], dev)])
    key = (op, value.shape, str(value.dtype))
    fn = _CROSS_FNS.get(key)
    if fn is None:
        def reduce_fn(x):
            if op == ReduceOp.SUM:
                return jnp.sum(x, axis=0)
            if op == ReduceOp.MAX:
                return jnp.max(x, axis=0)
            if op == ReduceOp.MIN:
                return jnp.min(x, axis=0)
            if op == ReduceOp.AVG:
                return jnp.mean(x, axis=0)
            if op == ReduceOp.PROD:
                return jnp.prod(x, axis=0)
            raise ValueError(op)

        fn = jax.jit(reduce_fn,
                     out_shardings=NamedSharding(mesh, P()))
        _CROSS_FNS[key] = fn
    out = fn(garr)
    return out.addressable_shards[0].data


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    axis = _group_axis(group)
    if _axis_in_scope(axis):
        def _ar(v):
            if op == ReduceOp.SUM:
                return jax.lax.psum(v, axis)
            if op == ReduceOp.MAX:
                return jax.lax.pmax(v, axis)
            if op == ReduceOp.MIN:
                return jax.lax.pmin(v, axis)
            if op == ReduceOp.AVG:
                return jax.lax.pmean(v, axis)
            if op == ReduceOp.PROD:
                return jnp.exp(jax.lax.psum(jnp.log(v), axis))
            raise ValueError(op)
        out = apply("all_reduce", _ar, tensor)
        tensor._rebind(out)
        return tensor
    if jax.process_count() > 1 and group is None:
        # eager cross-process collective (multi-controller runtime)
        tensor.set_value(_cross_process_all_reduce(tensor._value, op))
        return tensor
    # eager single-controller: group of compiled ranks not in scope → identity
    return tensor


def all_gather(tensor_list, tensor, group=None, sync_op=True, axis=0):
    ax = _group_axis(group)
    if _axis_in_scope(ax):
        out = apply("all_gather",
                    lambda v: jax.lax.all_gather(v, ax, tiled=False), tensor)
        n = out.shape[0]
        from ..ops.manipulation import unbind

        parts = unbind(out, 0)
        if isinstance(tensor_list, list):
            tensor_list.clear()
            tensor_list.extend(parts)
        return parts
    if isinstance(tensor_list, list):
        tensor_list.clear()
        tensor_list.append(tensor)
    return [tensor]


def all_gather_object(object_list, obj, group=None):
    object_list.clear()
    object_list.append(obj)
    return object_list


def reduce_scatter(tensor, tensor_list=None, op=ReduceOp.SUM, group=None,
                   sync_op=True):
    ax = _group_axis(group)
    if _axis_in_scope(ax):
        src = tensor_list if tensor_list is not None else tensor

        def _rs(v):
            return jax.lax.psum_scatter(v, ax, scatter_dimension=0, tiled=True)
        if isinstance(src, (list, tuple)):
            from ..ops.manipulation import concat

            src = concat(list(src), axis=0)
        out = apply("reduce_scatter", _rs, src)
        tensor._rebind(out)
        return tensor
    if tensor_list is not None and isinstance(tensor_list, (list, tuple)):
        tensor._rebind(tensor_list[0])
    return tensor


def alltoall(in_tensor_list, out_tensor_list=None, group=None, sync_op=True):
    ax = _group_axis(group)
    if _axis_in_scope(ax):
        from ..ops.manipulation import concat, unbind, stack

        x = stack(list(in_tensor_list), axis=0) \
            if isinstance(in_tensor_list, (list, tuple)) else in_tensor_list

        def _a2a(v):
            return jax.lax.all_to_all(v, ax, split_axis=0, concat_axis=0,
                                      tiled=False)
        out = apply("alltoall", _a2a, x)
        parts = unbind(out, 0)
        if isinstance(out_tensor_list, list):
            out_tensor_list.clear()
            out_tensor_list.extend(parts)
        return parts
    if isinstance(out_tensor_list, list):
        out_tensor_list.clear()
        out_tensor_list.extend(list(in_tensor_list))
    return list(in_tensor_list)


def alltoall_single(in_tensor, out_tensor=None, in_split_sizes=None,
                    out_split_sizes=None, group=None, sync_op=True):
    ax = _group_axis(group)
    if _axis_in_scope(ax):
        def _a2a(v):
            return jax.lax.all_to_all(v, ax, split_axis=0, concat_axis=0,
                                      tiled=True)
        out = apply("alltoall_single", _a2a, in_tensor)
        if out_tensor is not None:
            out_tensor._rebind(out)
            return out_tensor
        return out
    if out_tensor is not None:
        out_tensor._rebind(in_tensor)
        return out_tensor
    return in_tensor


def broadcast(tensor, src=0, group=None, sync_op=True):
    ax = _group_axis(group)
    if _axis_in_scope(ax):
        def _bc(v):
            # select src's value on every member of the axis
            full = jax.lax.all_gather(v, ax)
            return full[src]
        out = apply("broadcast", _bc, tensor)
        tensor._rebind(out)
        return tensor
    if jax.process_count() > 1 and group is None:
        from .env import get_rank

        v = tensor._value
        contrib = v if get_rank() == src else jnp.zeros_like(v)
        tensor.set_value(_cross_process_all_reduce(contrib, ReduceOp.SUM))
    return tensor


def broadcast_object_list(object_list, src=0, group=None):
    return object_list


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    # SPMD: reduce == all_reduce (every shard holds the result)
    return all_reduce(tensor, op, group, sync_op)


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    ax = _group_axis(group)
    if _axis_in_scope(ax):
        from ..ops.manipulation import stack

        x = stack(list(tensor_list), axis=0)

        def _sc(v):
            idx = jax.lax.axis_index(ax)
            return jnp.take(v, idx, axis=0)
        out = apply("scatter", _sc, x)
        tensor._rebind(out)
        return tensor
    if tensor_list:
        tensor._rebind(tensor_list[src])
    return tensor


def send(tensor, dst=0, group=None, sync_op=True):
    ax = _group_axis(group)
    if _axis_in_scope(ax):
        # point-to-point on a ring: collective_permute
        def _send(v):
            n = jax.lax.axis_size(ax)
            perm = [(i, dst) for i in range(n)]
            return jax.lax.ppermute(v, ax, perm)
        return apply("send", _send, tensor)
    raise RuntimeError(
        "eager send/recv has no peer process in the single-controller model; "
        "express P2P inside shard_map (ppermute) or use the pipeline API")


def recv(tensor, src=0, group=None, sync_op=True):
    ax = _group_axis(group)
    if _axis_in_scope(ax):
        def _recv(v):
            n = jax.lax.axis_size(ax)
            perm = [(src, i) for i in range(n)]
            return jax.lax.ppermute(v, ax, perm)
        out = apply("recv", _recv, tensor)
        tensor._rebind(out)
        return tensor
    raise RuntimeError(
        "eager send/recv has no peer process in the single-controller model; "
        "express P2P inside shard_map (ppermute) or use the pipeline API")


def isend(tensor, dst=0, group=None):
    return send(tensor, dst, group, sync_op=False)


def irecv(tensor, src=0, group=None):
    return recv(tensor, src, group, sync_op=False)


class _DoneTask:
    def wait(self):
        return True

    def is_completed(self):
        return True


def barrier(group=None):
    """Host-level barrier: single controller → trivially passed; multi-host
    uses the TCPStore barrier in distributed.launch."""
    jax.effects_barrier()
    return _DoneTask()


def wait(tensor, group=None, use_calc_stream=True):
    if isinstance(tensor, Tensor):
        try:
            tensor._value.block_until_ready()
        except Exception:
            pass
    return None


def stream_wait(*a, **k):
    return None


class ParallelMode:
    """Parallelism kind enum (reference:
    python/paddle/distributed/parallel.py ParallelMode)."""

    DATA_PARALLEL = 0
    TENSOR_PARALLEL = 1
    PIPELINE_PARALLEL = 2
    SHARDING_PARALLEL = 3


def split(x, size, operation, axis=0, num_partitions=1, gather_out=True,
          weight_attr=None, bias_attr=None, name=None):
    """Model-parallel linear/embedding in one call (reference:
    distributed/collective.py split — builds the partitioned weight and
    the collective).  TPU-native: delegates to the GSPMD parallel layers
    (parallel_layers.py), whose shardings compile to the same collectives
    the reference inserts by hand."""
    from .parallel_layers import (ColumnParallelLinear, RowParallelLinear,
                                  VocabParallelEmbedding)

    if operation == "embedding":
        layer = VocabParallelEmbedding(size[0], size[1],
                                       weight_attr=weight_attr)
        return layer(x)
    if operation != "linear":
        raise ValueError(f"split supports linear/embedding, got {operation}")
    has_bias = bias_attr is not False
    if axis == 0:
        layer = RowParallelLinear(size[0], size[1], weight_attr=weight_attr,
                                  has_bias=has_bias,
                                  input_is_parallel=False)
    elif axis == 1:
        layer = ColumnParallelLinear(size[0], size[1],
                                     weight_attr=weight_attr,
                                     has_bias=has_bias,
                                     gather_output=gather_out)
    else:
        raise ValueError("axis must be 0 (row) or 1 (column)")
    return layer(x)


# host-side barrier family over the TCPStore (reference: gloo_* in
# python/paddle/distributed/parallel.py — CPU-only barriers via gloo;
# the store is our gloo-position component)
_GLOO_STORE = None


def gloo_init_parallel_env(rank_id, rank_num, server_endpoint):
    global _GLOO_STORE
    from .store import TCPStore

    host, port = server_endpoint.rsplit(":", 1)
    _GLOO_STORE = TCPStore(host, int(port), is_master=(rank_id == 0))
    _GLOO_STORE.add("gloo/init", 1)
    import time

    # monotonic, not wall clock (hazard H111): an NTP step mid-
    # rendezvous would fire this timeout early or stretch it
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if _GLOO_STORE.add("gloo/init", 0) >= rank_num:
            return
        time.sleep(0.01)
    raise TimeoutError("gloo_init_parallel_env rendezvous timed out")


_gloo_barrier_round = [0]


def gloo_barrier():
    if _GLOO_STORE is None:
        raise RuntimeError("call gloo_init_parallel_env first")
    _gloo_barrier_round[0] += 1
    key = f"gloo/barrier/{_gloo_barrier_round[0]}"
    world = _GLOO_STORE.add("gloo/init", 0)
    _GLOO_STORE.add(key, 1)
    import time

    deadline = time.monotonic() + 30      # H111: never the wall clock
    while time.monotonic() < deadline:
        if _GLOO_STORE.add(key, 0) >= world:
            return
        time.sleep(0.01)
    raise TimeoutError("gloo_barrier timed out")


def gloo_release():
    global _GLOO_STORE
    _GLOO_STORE = None
