"""Hybrid-parallel topology — the mesh abstraction.

Reference: python/paddle/distributed/fleet/base/topology.py:36 (CommunicateTopology,
N-D cartesian rank mesh) and :117 (HybridCommunicateGroup building dp/mp/pp/sharding
groups). The API is kept verbatim; TPU-natively the topology *is* a
jax.sharding.Mesh — `build_mesh()` returns one with axes named after the topology
dims, and every "communication group" is just an axis name for psum/ppermute under
shard_map (no comm objects, no ring ids).
"""
from __future__ import annotations

import itertools
from functools import reduce
from typing import Dict, List, Optional, Sequence

import numpy as np

import jax
from jax.sharding import Mesh


class ParallelMode:
    DATA_PARALLEL = 0
    TENSOR_PARALLEL = 1
    PIPELINE_PARALLEL = 2
    SHARDING_PARALLEL = 3


class CommunicateTopology:
    def __init__(self, hybrid_group_names=("data", "pipe", "sharding", "model"),
                 dims=(1, 1, 1, 1)):
        self._parallel_names = list(hybrid_group_names)
        self._dims = list(dims)
        self.coordinate = collections_namedtuple(self._parallel_names)
        self._world = np.arange(int(np.prod(self._dims))).reshape(self._dims)
        ranks = list(itertools.product(*(range(d) for d in self._dims)))
        self._coord2rank = {c: int(self._world[c]) for c in ranks}
        self._rank2coord = {v: k for k, v in self._coord2rank.items()}

    def get_hybrid_group_names(self):
        return self._parallel_names

    def get_dim(self, axis_name):
        return self._dims[self._parallel_names.index(axis_name)]

    get_dim_size = get_dim

    def world_size(self):
        return int(self._world.size)

    def get_rank(self, **kwargs):
        coord = tuple(kwargs[name] for name in self._parallel_names)
        return self._coord2rank[coord]

    def get_coord(self, rank):
        return self.coordinate(*self._rank2coord[rank])

    def get_axis_list(self, axis_name, index):
        """All ranks whose coordinate on `axis_name` equals index."""
        axis = self._parallel_names.index(axis_name)
        return sorted(int(r) for c, r in self._coord2rank.items()
                      if c[axis] == index)

    def get_comm_list(self, axis_name):
        """List of rank-groups along `axis_name` (reference topology.py:86)."""
        axis = self._parallel_names.index(axis_name)
        other_dims = [d for i, d in enumerate(self._dims) if i != axis]
        groups = []
        for other in itertools.product(*(range(d) for d in other_dims)):
            group = []
            for i in range(self._dims[axis]):
                coord = list(other)
                coord.insert(axis, i)
                group.append(self._coord2rank[tuple(coord)])
            groups.append(group)
        return groups

    def get_rank_from_stage(self, global_rank, **kwargs):
        coord = self.get_coord(global_rank)
        tf = coord._replace(**kwargs)._asdict()
        return self.get_rank(**tf)


def collections_namedtuple(names):
    import collections
    return collections.namedtuple("Coordinate", names)


class HybridCommunicateGroup:
    """Reference topology.py:117. Holds per-axis "groups" — here lightweight
    _AxisGroup handles naming a mesh axis — plus the rank bookkeeping models use
    (degree/rank per parallelism kind)."""

    def __init__(self, topology: CommunicateTopology, global_rank: int = None):
        from .parallel_env import ParallelEnv
        self._topo = topology
        self.global_rank = (global_rank if global_rank is not None
                            else ParallelEnv().rank)
        self.nranks = topology.world_size()

        self._dp_degree = topology.get_dim("data")
        self._pp_degree = topology.get_dim("pipe")
        self._sharding_degree = topology.get_dim("sharding")
        self._mp_degree = topology.get_dim("model")

        coord = topology.get_coord(self.global_rank % max(self.nranks, 1))
        self._dp_rank = coord.data
        self._pp_rank = coord.pipe
        self._sharding_rank = coord.sharding
        self._mp_rank = coord.model

        self._dp_group = _AxisGroup("data", topology, self.global_rank)
        self._pp_group = _AxisGroup("pipe", topology, self.global_rank)
        self._sharding_group = _AxisGroup("sharding", topology,
                                          self.global_rank)
        self._mp_group = _AxisGroup("model", topology, self.global_rank)
        # parity-plus axes (absent from the reference topology.py:36): expert
        # parallel (alltoall primitive, reference collective.py:1456) and
        # sequence parallel
        names = topology.get_hybrid_group_names()
        self._ep_degree = topology.get_dim("ep") if "ep" in names else 1
        self._ep_rank = getattr(coord, "ep", 0) if "ep" in names else 0
        self._ep_group = (_AxisGroup("ep", topology, self.global_rank)
                          if "ep" in names else None)
        self._sep_degree = topology.get_dim("sep") if "sep" in names else 1

    # parallel mode dispatch (fleet_base distributed_model uses this)
    def get_parallel_mode(self):
        if (self._mp_degree == 1 and self._pp_degree == 1
                and self._sharding_degree == 1):
            return ParallelMode.DATA_PARALLEL
        if self._pp_degree > 1:
            return ParallelMode.PIPELINE_PARALLEL
        if self._mp_degree > 1:
            return ParallelMode.TENSOR_PARALLEL
        return ParallelMode.SHARDING_PARALLEL

    def topology(self):
        return self._topo

    def get_global_rank(self):
        return self.global_rank

    # data parallel
    def get_data_parallel_rank(self):
        return self._dp_rank

    def get_data_parallel_world_size(self):
        return self._dp_degree

    def get_data_parallel_group(self):
        return self._dp_group

    def get_data_parallel_group_src_rank(self):
        return self._dp_group.ranks[0]

    # model (tensor) parallel
    def get_model_parallel_rank(self):
        return self._mp_rank

    def get_model_parallel_world_size(self):
        return self._mp_degree

    def get_model_parallel_group(self):
        return self._mp_group

    def get_model_parallel_group_src_rank(self):
        return self._mp_group.ranks[0]

    # pipeline parallel
    def get_stage_id(self):
        return self._pp_rank

    def get_pipe_parallel_rank(self):
        return self._pp_rank

    def get_pipe_parallel_world_size(self):
        return self._pp_degree

    def get_pipe_parallel_group(self):
        return self._pp_group

    def is_first_stage(self):
        return self._pp_rank == 0

    def is_last_stage(self):
        return self._pp_rank == self._pp_degree - 1

    # sharding
    def get_sharding_parallel_rank(self):
        return self._sharding_rank

    def get_sharding_parallel_world_size(self):
        return self._sharding_degree

    def get_sharding_parallel_group(self):
        return self._sharding_group

    def get_sharding_parallel_group_src_rank(self):
        return self._sharding_group.ranks[0]

    # expert parallel (parity-plus)
    def get_expert_parallel_rank(self):
        return self._ep_rank

    def get_expert_parallel_world_size(self):
        return self._ep_degree

    def get_expert_parallel_group(self):
        return self._ep_group

    # p2p neighbours (reference _build_p2p_lists:173)
    def get_p2p_groups(self):
        prev_stage = (self._pp_rank - 1) % self._pp_degree
        next_stage = (self._pp_rank + 1) % self._pp_degree
        return prev_stage, next_stage

    # mesh factory — the TPU-native heart of the topology
    def build_mesh(self, devices=None) -> Mesh:
        return build_mesh_from_dims(
            dict(zip(self._topo.get_hybrid_group_names(), self._topo._dims)),
            devices)


class _AxisGroup:
    """A "communication group" = a named mesh axis + its rank list."""

    def __init__(self, axis_name: str, topo: CommunicateTopology,
                 global_rank: int):
        self.axis_name = axis_name
        self._topo = topo
        coord = topo.get_coord(global_rank % max(topo.world_size(), 1))
        idx = topo.get_hybrid_group_names().index(axis_name)
        # the group containing global_rank along this axis
        fixed = {n: getattr(coord, n) for n in topo.get_hybrid_group_names()
                 if n != axis_name}
        self.ranks = [topo.get_rank(**{**fixed, axis_name: i})
                      for i in range(topo.get_dim(axis_name))]
        self.nranks = len(self.ranks)
        self.rank = self.ranks.index(global_rank) if global_rank in self.ranks \
            else -1
        self.id = idx + 1  # ring-id analog; 0 is the global group

    @property
    def world_size(self):
        return self.nranks

    def get_group_rank(self, rank):
        return self.ranks.index(rank) if rank in self.ranks else -1

    def __repr__(self):
        return f"Group(axis={self.axis_name}, ranks={self.ranks})"


def build_mesh_from_dims(dims: Dict[str, int], devices=None) -> Mesh:
    """Create a jax Mesh with the given {axis: size} layout.

    Axis order follows the dict (reference order: data, pipe, sharding, model).
    Axes of size 1 are kept so PartitionSpecs can always name them. On real TPU
    slices the default device order already follows the physical torus; the
    innermost axis (model) gets the fastest-varying devices → TP collectives ride
    the shortest ICI hops.

    With `devices=None` the topology must use every accelerator chip the
    process holds: a smaller one would quietly run on the first chips and
    leave the rest idle (the process owns them all either way). To use a
    subset on purpose, pass it as `devices=`. Virtual CPU devices cost
    nothing idle, so CPU meshes may still be smaller than the host's count.
    """
    devs = list(devices) if devices is not None else jax.devices()
    total = reduce(lambda a, b: a * b, dims.values(), 1)
    if total > len(devs):
        raise ValueError(
            f"topology {dims} needs {total} devices, have {len(devs)}")
    if devices is None and total < len(devs) and devs[0].platform != "cpu":
        raise ValueError(
            f"topology {dims} uses {total} of this process's {len(devs)} "
            f"{devs[0].platform} chips and would leave the rest idle; size "
            "hybrid_configs to the device count (dp_degree=-1 fills it) or "
            "pass the subset explicitly as devices=")
    arr = np.array(devs[:total]).reshape(tuple(dims.values()))
    return Mesh(arr, tuple(dims.keys()))


_GLOBAL_HCG: List[Optional[HybridCommunicateGroup]] = [None]
_GLOBAL_MESH: List[Optional[Mesh]] = [None]


def set_hybrid_communicate_group(hcg: HybridCommunicateGroup):
    _GLOBAL_HCG[0] = hcg
    _GLOBAL_MESH[0] = hcg.build_mesh()


def get_hybrid_communicate_group() -> Optional[HybridCommunicateGroup]:
    return _GLOBAL_HCG[0]


def get_mesh() -> Optional[Mesh]:
    return _GLOBAL_MESH[0]


def set_mesh(mesh: Mesh):
    _GLOBAL_MESH[0] = mesh
