"""Device mesh + hybrid topology.

TPU-native replacement for the reference 4-axis process topology
(/root/reference/python/paddle/distributed/fleet/base/topology.py:51
CommunicateTopology, :133 HybridCommunicateGroup): instead of building NCCL
communicators per axis, we build ONE jax.sharding.Mesh whose named axes
(dp/pp/sharding/mp/sp/ep subsets) drive GSPMD partitioning; per-axis "groups"
are views over mesh axes.
"""
from __future__ import annotations

import contextlib
import itertools
from typing import Dict, List, Optional, Sequence

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

_GLOBAL_MESH: Optional[Mesh] = None
_GLOBAL_HCG: Optional["HybridCommunicateGroup"] = None


def shard_map_compat(fn, mesh, in_specs, out_specs):
    """jax.shard_map with the varying-manual-axes check off (the
    callers' bodies mix replicated and per-shard values freely)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def init_mesh(axes: Dict[str, int], devices=None) -> Mesh:
    """Create and install the global mesh, e.g. init_mesh({"dp": 2, "mp": 4}).

    Axis sizes must multiply to the device count (axes of size 1 allowed).
    """
    global _GLOBAL_MESH
    devices = devices if devices is not None else jax.devices()
    names = [k for k, v in axes.items()]
    sizes = [int(v) for v in axes.values()]
    total = int(np.prod(sizes))
    if total != len(devices):
        raise ValueError(
            f"mesh {axes} needs {total} devices, have {len(devices)}")
    arr = np.asarray(devices).reshape(sizes)
    _GLOBAL_MESH = Mesh(arr, tuple(names))
    return _GLOBAL_MESH


def get_mesh() -> Optional[Mesh]:
    return _GLOBAL_MESH


def mesh_live() -> bool:
    """A device mesh is in force at this trace: the global fleet mesh, a
    registered ``MeshExecutor``'s, or a manual-mp ``shard_map`` stage.
    The Pallas kernels have no partitioning rule (Mosaic: "kernels
    cannot be automatically partitioned"), so under a mesh every kernel
    site takes its XLA form until the kernels are wrapped in
    ``shard_map`` (ROADMAP Speed 9)."""
    from .executor import active_mesh
    from .parallel_layers import manual_axis

    return get_mesh() is not None or active_mesh() is not None \
        or manual_axis("mp")[0] is not None


def reset_mesh():
    """Clear the process-global mesh + HCG (the teardown half of
    fleet.init; reference analog: fleet_base stop_worker releasing the
    communication groups).  Callers should prefer fleet.shutdown()."""
    global _GLOBAL_MESH, _GLOBAL_HCG
    _GLOBAL_MESH = None
    _GLOBAL_HCG = None


def set_mesh(mesh: Mesh):
    global _GLOBAL_MESH
    _GLOBAL_MESH = mesh
    return mesh


@contextlib.contextmanager
def use_mesh(mesh):
    """Temporarily install ``mesh`` as the global mesh (restored on
    exit).  Lets a step trace against a specific — possibly abstract —
    mesh without clobbering the process-global one."""
    global _GLOBAL_MESH
    prev = _GLOBAL_MESH
    _GLOBAL_MESH = mesh
    try:
        yield mesh
    finally:
        _GLOBAL_MESH = prev


def abstract_mesh(axes: Dict[str, int]):
    """A devices-free ``jax.sharding.AbstractMesh`` over named axes, e.g.
    ``abstract_mesh({"data": 2, "sp": 2})``.  Good enough for tracing
    (shard_map, with_sharding_constraint) under ``make_jaxpr`` — which is
    all the static analyzers need — without claiming real chips."""
    from jax.sharding import AbstractMesh

    pairs = tuple((str(k), int(v)) for k, v in axes.items())
    try:
        return AbstractMesh(pairs)
    except TypeError:
        # newer signature: AbstractMesh(shape_tuple, axis_names)
        return AbstractMesh(tuple(s for _, s in pairs),
                            tuple(n for n, _ in pairs))


def fleet_mesh(dp_degree=1, mp_degree=1, pp_degree=1, sharding_degree=1,
               sp_degree=1, ep_degree=1, devices=None) -> Mesh:
    """Fleet-style hybrid mesh with canonical axis order [dp, pp, sharding,
    sp, ep, mp] (the reference's order is [data, pipe, sharding, model],
    topology.py:159)."""
    axes = {}
    for name, deg in (("dp", dp_degree), ("pp", pp_degree),
                      ("sharding", sharding_degree), ("sp", sp_degree),
                      ("ep", ep_degree), ("mp", mp_degree)):
        if deg and deg > 1:
            axes[name] = deg
    if not axes:
        axes = {"dp": 1}
    n = int(np.prod(list(axes.values())))
    devices = devices if devices is not None else jax.devices()
    if n != len(devices):
        # pad with a trailing dp axis if degrees underspecify the devices
        if len(devices) % n == 0 and "dp" not in axes:
            axes = {"dp": len(devices) // n, **axes}
        elif len(devices) % n == 0 and "dp" in axes:
            axes["dp"] *= len(devices) // n
        else:
            raise ValueError(
                f"degrees {axes} incompatible with {len(devices)} devices")
    return init_mesh(axes, devices)


class CommunicateTopology:
    """Rank/coordinate bookkeeping over hybrid axes (reference:
    topology.py:51)."""

    def __init__(self, hybrid_group_names=("data", "pipe", "sharding", "model"),
                 dims=(1, 1, 1, 1)):
        self._parallel_names = list(hybrid_group_names)
        self._dims = list(dims)
        self.coordinate = list(itertools.product(*[range(d) for d in dims]))
        self._coord2rank = {c: i for i, c in enumerate(self.coordinate)}

    def get_hybrid_group_names(self):
        return self._parallel_names

    def get_dim(self, axis_name):
        return self._dims[self._parallel_names.index(axis_name)]

    get_dim_size = get_dim

    def world_size(self):
        return len(self.coordinate)

    def get_rank(self, **kwargs):
        coord = tuple(kwargs[name] for name in self._parallel_names)
        return self._coord2rank[coord]

    def get_coord(self, rank):
        return self.coordinate[rank]

    def get_axis_list(self, axis_name, index):
        axis = self._parallel_names.index(axis_name)
        return [r for r, c in enumerate(self.coordinate) if c[axis] == index]

    def get_comm_list(self, axis_name):
        """All groups along axis_name: lists of ranks varying only that axis."""
        axis = self._parallel_names.index(axis_name)
        others = [i for i in range(len(self._dims)) if i != axis]
        groups = {}
        for rank, coord in enumerate(self.coordinate):
            key = tuple(coord[i] for i in others)
            groups.setdefault(key, []).append(rank)
        return list(groups.values())


class _AxisGroup:
    """A communication 'group' = one mesh axis (or the trivial group)."""

    def __init__(self, axis_name: Optional[str], nranks: int, rank: int,
                 ranks: Sequence[int]):
        self.axis_name = axis_name
        self.nranks = nranks
        self.rank = rank
        self.ranks = list(ranks)
        self.id = hash((axis_name, tuple(ranks))) & 0x7FFFFFFF

    @property
    def world_size(self):
        return self.nranks

    def get_group_rank(self, rank):
        return self.ranks.index(rank) if rank in self.ranks else -1

    def process_group(self):
        return self


class HybridCommunicateGroup:
    """Reference topology.py:133 analog over the global Mesh."""

    def __init__(self, topology: Optional[CommunicateTopology] = None,
                 mesh: Optional[Mesh] = None):
        self._mesh = mesh or get_mesh()
        self._topo = topology
        global _GLOBAL_HCG
        _GLOBAL_HCG = self

    def _axis_size(self, names):
        if self._mesh is None:
            return 1
        size = 1
        for n in names:
            if n in self._mesh.shape:
                size *= self._mesh.shape[n]
        return size

    # --- degrees
    def get_data_parallel_world_size(self):
        return self._axis_size(["dp"])

    def get_model_parallel_world_size(self):
        return self._axis_size(["mp"])

    def get_pipe_parallel_world_size(self):
        return self._axis_size(["pp"])

    def get_sharding_parallel_world_size(self):
        return self._axis_size(["sharding"])

    def get_sep_parallel_world_size(self):
        return self._axis_size(["sp"])

    def get_expert_parallel_world_size(self):
        return self._axis_size(["ep"])

    # --- ranks (single-controller SPMD: the driving process is rank 0 on
    # every axis; per-device ranks exist only inside compiled programs)
    def get_data_parallel_rank(self):
        return 0

    def get_model_parallel_rank(self):
        return 0

    def get_stage_id(self):
        return 0

    def get_sharding_parallel_rank(self):
        return 0

    # --- groups
    def _group(self, axis):
        size = self._axis_size([axis])
        return _AxisGroup(axis if size > 1 else None, size, 0, range(size))

    def get_data_parallel_group(self):
        return self._group("dp")

    def get_model_parallel_group(self):
        return self._group("mp")

    def get_pipe_parallel_group(self):
        return self._group("pp")

    def get_sharding_parallel_group(self):
        return self._group("sharding")

    def get_expert_parallel_group(self):
        return self._group("ep")

    def get_data_parallel_group_src_rank(self):
        return 0

    def get_model_parallel_group_src_rank(self):
        return 0

    # pipeline helpers
    def is_first_stage(self):
        return self.get_stage_id() == 0

    def is_last_stage(self):
        return self.get_stage_id() == self.get_pipe_parallel_world_size() - 1

    @property
    def nranks(self):
        return self._mesh.size if self._mesh is not None else 1

    def topology(self):
        return self._topo

    def get_parallel_mode(self):
        if self.get_pipe_parallel_world_size() > 1:
            return "pipeline"
        if self.get_sharding_parallel_world_size() > 1:
            return "sharding"
        if self.get_model_parallel_world_size() > 1:
            return "model"
        return "data"


def get_hybrid_communicate_group() -> Optional[HybridCommunicateGroup]:
    return _GLOBAL_HCG


class ProcessMesh:
    """auto_parallel ProcessMesh analog (reference:
    python/paddle/distributed/auto_parallel/process_mesh.py) — a named view
    over device ids that converts to a jax Mesh."""

    def __init__(self, mesh=None, dim_names=None, shape=None, process_ids=None):
        if mesh is not None:
            arr = np.asarray(mesh)
        else:
            arr = np.asarray(process_ids).reshape(shape)
        self._shape = list(arr.shape)
        self._ids = arr.flatten().tolist()
        self._dim_names = list(dim_names) if dim_names else [
            f"d{i}" for i in range(arr.ndim)]

    @property
    def shape(self):
        return self._shape

    @property
    def process_ids(self):
        return self._ids

    @property
    def dim_names(self):
        return self._dim_names

    @property
    def ndim(self):
        return len(self._shape)

    def to_jax_mesh(self) -> Mesh:
        devices = jax.devices()
        arr = np.asarray([devices[i] for i in self._ids]).reshape(self._shape)
        return Mesh(arr, tuple(self._dim_names))

    def __eq__(self, other):
        return (isinstance(other, ProcessMesh) and self._shape == other._shape
                and self._ids == other._ids)

    def __repr__(self):
        return f"ProcessMesh(shape={self._shape}, dims={self._dim_names})"
