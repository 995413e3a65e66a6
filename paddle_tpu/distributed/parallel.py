"""init_parallel_env / rank info / DataParallel wrapper.

Parity: /root/reference/python/paddle/distributed/parallel.py:917 (env init
creates TCPStore + default ProcessGroup) and :190 (DataParallel). TPU-native:
``jax.distributed.initialize`` + the TPU runtime's own coordination replace
TCPStore/NCCL bootstrap; a Mesh replaces the default group; DataParallel
reduces to batch-axis sharding under jit (GSPMD inserts the grad psum), with
an eager grad-hook path kept for API/debug parity with EagerReducer.
"""
from __future__ import annotations

import os

import jax

from ..nn.layer import Layer
from .mesh import (HybridCommunicateGroup, get_hybrid_communicate_group,
                   set_hybrid_communicate_group)
from .strategy import DistributedStrategy

__all__ = [
    "init_parallel_env", "get_rank", "get_world_size", "ParallelEnv", "DataParallel",
]

_initialized = False


def init_parallel_env(strategy: DistributedStrategy | None = None):
    """Initialize distributed state. Multi-host: call jax.distributed.initialize
    (driven by launch CLI env); single-host: build the mesh over local devices."""
    global _initialized
    if _initialized:
        # the process-level bootstrap (jax.distributed.initialize) must run
        # once, but a torn-down mesh (tests call
        # set_hybrid_communicate_group(None) between modules) must be
        # rebuilt — otherwise every later collective fails "call
        # init_parallel_env first" even though the caller just did
        if get_hybrid_communicate_group() is not None:
            return ParallelEnv()
        if strategy is None:
            strategy = DistributedStrategy()
            strategy.hybrid_configs.dp_degree = jax.device_count()
        set_hybrid_communicate_group(HybridCommunicateGroup(strategy))
        return ParallelEnv()
    coord = os.environ.get("PADDLE_TPU_COORDINATOR")
    nproc = int(os.environ.get("PADDLE_TPU_NUM_PROCESSES", "1"))
    if coord and nproc > 1:
        # must run BEFORE any backend use (jax.devices()/process_count()
        # would freeze a single-process topology); multi-proc CPU rides the
        # gloo collectives implementation
        if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
            try:
                jax.config.update("jax_cpu_collectives_implementation", "gloo")
            except Exception:  # lint: allow-silent(older jax without the knob; mpi/none fallback)
                pass
        try:
            jax.distributed.initialize(
                coordinator_address=coord,
                num_processes=nproc,
                process_id=int(os.environ.get("PADDLE_TPU_PROCESS_ID", "0")),
            )
        except RuntimeError as e:
            if "already" not in str(e).lower():
                raise
    if strategy is None:
        strategy = DistributedStrategy()
        # default: pure DP over every device
        strategy.hybrid_configs.dp_degree = jax.device_count()
    hcg = HybridCommunicateGroup(strategy)
    set_hybrid_communicate_group(hcg)
    _initialized = True
    return ParallelEnv()


def get_rank(group=None):
    return jax.process_index()


def get_world_size(group=None):
    from .mesh import get_hybrid_communicate_group

    hcg = get_hybrid_communicate_group()
    if hcg is None:
        return jax.device_count()
    return hcg.nranks


class ParallelEnv:
    @property
    def rank(self):
        return get_rank()

    @property
    def world_size(self):
        return get_world_size()

    @property
    def device_id(self):
        return 0

    local_rank = rank
    nranks = world_size


class DataParallel(Layer):
    """paddle.DataParallel parity wrapper.

    Under the jitted train path, data parallelism is expressed by sharding the
    batch dim over the 'dp' axis — gradients are reduced by GSPMD, so this
    wrapper only marks the module. For eager debugging it registers grad
    hooks doing an explicit all_reduce (EagerReducer's observable behavior,
    /root/reference/paddle/fluid/distributed/collective/reducer.cc — without
    bucketing: XLA fuses collectives instead).
    """

    def __init__(self, layers, strategy=None, comm_buffer_size=25,
                 last_comm_buffer_size=1, find_unused_parameters=False, group=None):
        super().__init__()
        self._layers = layers
        self._group = group
        self._eager_allreduce = False  # enable for eager-mode debugging
        if self._eager_allreduce:
            self._register_hooks()

    def _register_hooks(self):
        from . import collective

        def make_hook():
            def hook(grad):
                return collective.all_reduce(grad, op=collective.ReduceOp.AVG, group=self._group)

            return hook

        for p in self._layers.parameters():
            p.register_hook(make_hook())

    def forward(self, *inputs, **kwargs):
        return self._layers(*inputs, **kwargs)

    def state_dict(self, *args, **kwargs):
        return self._layers.state_dict(*args, **kwargs)

    def set_state_dict(self, *args, **kwargs):
        return self._layers.set_state_dict(*args, **kwargs)

    @property
    def parameters_layer(self):
        return self._layers

    def scale_loss(self, loss):
        return loss

    def apply_collective_grads(self):
        from . import collective

        for p in self._layers.parameters():
            if p._grad is not None:
                t = collective.all_reduce(
                    p.grad, op=collective.ReduceOp.AVG, group=self._group)
                p._grad = t._value
