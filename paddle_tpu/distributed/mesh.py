"""Mesh topology: the TPU-native HybridCommunicateGroup.

The reference builds a 4-D cartesian process topology and one NCCL
communicator per axis (/root/reference/python/paddle/distributed/fleet/base/
topology.py:58,144). Here ONE ``jax.sharding.Mesh`` over ICI/DCN replaces all
communicators: axes (dp, sharding, pp, sep, mp) are named mesh dims; each
reference sub-group becomes a mesh axis name usable in PartitionSpec /
shard_map, and XLA emits the collectives (SURVEY §5.8).

Axis order puts mp innermost so tensor-parallel collectives ride the
fastest ICI links; dp/pp outermost can span DCN
(jax-ml.github.io/scaling-book recipe).
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .strategy import DistributedStrategy

__all__ = [
    "HybridCommunicateGroup", "build_mesh", "get_hybrid_communicate_group",
    "set_hybrid_communicate_group", "P", "current_mesh",
]

P = PartitionSpec

_GLOBAL_HCG = None

# canonical axis order, outermost → innermost
AXIS_ORDER = ("pp", "dp", "sharding", "sep", "ep", "mp")


def build_mesh(strategy: DistributedStrategy | None = None, devices=None,
               degrees: dict | None = None) -> Mesh:
    """Build the hybrid mesh from strategy degrees (or an explicit dict)."""
    if degrees is None:
        h = (strategy or DistributedStrategy()).hybrid_configs
        degrees = {
            "pp": h.pp_degree, "dp": h.dp_degree, "sharding": h.sharding_degree,
            "sep": h.sep_degree, "ep": h.ep_degree, "mp": h.mp_degree,
        }
    shape = [int(degrees.get(a, 1)) for a in AXIS_ORDER]
    total = int(np.prod(shape))
    if devices is None:
        devices = jax.devices()
    if total > len(devices):
        raise ValueError(
            f"mesh needs {total} devices ({dict(zip(AXIS_ORDER, shape))}), "
            f"only {len(devices)} available")
    dev_array = np.array(devices[:total]).reshape(shape)
    return Mesh(dev_array, AXIS_ORDER)


class HybridCommunicateGroup:
    """Rank bookkeeping over the mesh (reference HybridCommunicateGroup:144).

    The reference exposes per-axis communicators + ranks; here ranks are
    derived from the device coords of ``jax.process_index`` addressable
    devices, and "groups" are just axis names.
    """

    def __init__(self, strategy: DistributedStrategy | None = None, mesh: Mesh | None = None):
        self.strategy = strategy or DistributedStrategy()
        self.mesh = mesh if mesh is not None else build_mesh(self.strategy)
        self._shape = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))

    # -- degrees ----------------------------------------------------------
    def get_data_parallel_world_size(self):
        return self._shape.get("dp", 1)

    def get_model_parallel_world_size(self):
        return self._shape.get("mp", 1)

    def get_pipe_parallel_world_size(self):
        return self._shape.get("pp", 1)

    def get_sharding_parallel_world_size(self):
        return self._shape.get("sharding", 1)

    def get_sep_parallel_world_size(self):
        return self._shape.get("sep", 1)

    def get_expert_parallel_world_size(self):
        return self._shape.get("ep", 1)

    @property
    def nranks(self):
        return int(np.prod(list(self._shape.values())))

    # -- coords for the current process's first device --------------------
    def _coord(self, axis):
        dev = self.mesh.devices.flat[0]
        local = jax.local_devices()[0]
        idx = np.argwhere(self.mesh.devices == local)
        if idx.size == 0:
            idx = np.zeros((1, len(self.mesh.axis_names)), np.int64)
        return int(idx[0][self.mesh.axis_names.index(axis)])

    def get_data_parallel_rank(self):
        return self._coord("dp")

    def get_model_parallel_rank(self):
        return self._coord("mp")

    def get_stage_id(self):
        return self._coord("pp")

    def get_sharding_parallel_rank(self):
        return self._coord("sharding")

    # -- axis name handles (the reference returns comm groups) ------------
    def get_data_parallel_group(self):
        return "dp"

    def get_model_parallel_group(self):
        return "mp"

    def get_pipe_parallel_group(self):
        return "pp"

    def get_sharding_parallel_group(self):
        return "sharding"

    def get_sep_parallel_group(self):
        return "sep"

    def get_expert_parallel_group(self):
        return "ep"

    def is_first_stage(self):
        return self.get_stage_id() == 0

    def is_last_stage(self):
        return self.get_stage_id() == self.get_pipe_parallel_world_size() - 1

    # -- sharding helpers -------------------------------------------------
    def sharding_for(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, PartitionSpec(*spec))

    def topology(self):
        return self._shape


def set_hybrid_communicate_group(hcg: HybridCommunicateGroup):
    global _GLOBAL_HCG
    _GLOBAL_HCG = hcg


def get_hybrid_communicate_group() -> HybridCommunicateGroup | None:
    return _GLOBAL_HCG


def current_mesh() -> Mesh | None:
    hcg = get_hybrid_communicate_group()
    return hcg.mesh if hcg is not None else None
