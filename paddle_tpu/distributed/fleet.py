"""Fleet facade (reference: /root/reference/python/paddle/distributed/fleet/
fleet.py:99,167,1044 — init/distributed_model/distributed_optimizer)."""
from __future__ import annotations

import jax

from .mesh import HybridCommunicateGroup, get_hybrid_communicate_group, set_hybrid_communicate_group
from .parallel import DataParallel
from .strategy import DistributedStrategy

__all__ = [
    "init", "distributed_model", "distributed_optimizer", "get_hybrid_communicate_group",
    "worker_index", "worker_num", "is_first_worker", "DistributedStrategy",
]

_strategy: DistributedStrategy | None = None


def init(role_maker=None, is_collective=True, strategy=None, log_level="INFO"):
    global _strategy
    _strategy = strategy or DistributedStrategy()
    if _strategy.world_degree == 1:
        # default: all devices to data parallel, reference-style
        _strategy.hybrid_configs.dp_degree = jax.device_count()
    hcg = HybridCommunicateGroup(_strategy)
    set_hybrid_communicate_group(hcg)
    return hcg


def get_strategy() -> DistributedStrategy | None:
    return _strategy


def distributed_model(model):
    """Wrap per parallel mode (reference fleet/model.py:30,126-165).

    TP layers already carry sharding annotations; PP wrapping happens in
    PipelineLayer; so DP wrapping is the only structural change here — the
    real composition happens in DistributedEngine at train-step build time.
    """
    hcg = get_hybrid_communicate_group()
    if hcg is None:
        init()
        hcg = get_hybrid_communicate_group()
    if hcg.get_data_parallel_world_size() > 1 and \
            hcg.get_model_parallel_world_size() == 1 and \
            hcg.get_pipe_parallel_world_size() == 1:
        return DataParallel(model)
    return model


def distributed_optimizer(optimizer, strategy=None):
    """Reference returns HybridParallelOptimizer (grad clip across mesh axes,
    hybrid_parallel_optimizer.py:238). Mesh-global grad norms fall out of
    GSPMD automatically (norm reductions span the whole mesh inside jit), so
    the optimizer passes through; sharded-state placement is applied by
    DistributedEngine."""
    return optimizer


def worker_index():
    return jax.process_index()


def worker_num():
    return jax.process_count()


def is_first_worker():
    return jax.process_index() == 0
