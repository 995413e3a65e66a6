"""Eager collective API over mesh axes.

Parity surface: paddle.distributed.{all_reduce, all_gather, reduce_scatter,
broadcast, all_to_all, send/recv(ppermute), scatter, reduce, barrier}
(/root/reference/python/paddle/distributed/communication/*.py) backed by
ProcessGroup+NCCL in the reference. TPU-native: each collective is a
``shard_map`` over the current Mesh axis, compiled by XLA onto ICI — there is
no transport code here (SURVEY §5.8). The eager API exists for debugging and
for the collective test-suite shape; production paths let GSPMD infer
collectives from shardings instead.

Data model: a "distributed tensor" is a jax array sharded over the group axis
(each mesh-axis slice plays the role of one reference rank). Helpers
``shard_to_group``/``unshard`` move between host batches and group-sharded
arrays for tests.
"""
from __future__ import annotations

import functools
import threading
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map as _shard_map

from .. import telemetry
from ..telemetry import cluster as _cluster
from ..telemetry import perf as _perf
from ..core.tensor import Tensor
from ..framework.flags import flag_value
from ..utils import faults
from .mesh import HybridCommunicateGroup, get_hybrid_communicate_group

__all__ = [
    "ReduceOp", "all_reduce", "all_gather", "reduce_scatter", "broadcast",
    "all_to_all", "alltoall", "reduce", "scatter", "barrier", "send", "recv",
    "ppermute", "shard_to_group", "unshard", "new_group", "get_group",
    "CollectiveTimeoutError",
]


class CollectiveTimeoutError(TimeoutError):
    """A guarded collective did not complete within
    ``FLAGS_collective_timeout_s``; the message names the op, the group
    axis, its size, and this process's rank — the first thing an operator
    needs when one host of a pod wedges."""


def _collective_metrics():
    """Per-op telemetry families (get-or-create is idempotent; the labeled
    child resolve below is one dict hit per call)."""
    reg = telemetry.registry()
    return (
        reg.counter("collective_calls_total",
                    "eager collective launches", ("op",)),
        reg.counter("collective_bytes_total",
                    "input bytes entering eager collectives", ("op",)),
        reg.counter("collective_timeouts_total",
                    "collectives killed by the timeout guard", ("op",)),
        reg.histogram("collective_seconds",
                      "wall time of one eager collective", ("op",)),
    )


_M_CALLS, _M_BYTES, _M_TIMEOUTS, _M_SECONDS = _collective_metrics()


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


class Group:
    """A mesh-axis handle (the reference's ProcessGroup analogue)."""

    def __init__(self, axis: str, hcg: HybridCommunicateGroup):
        self.axis = axis
        self.hcg = hcg

    @property
    def nranks(self):
        return dict(zip(self.hcg.mesh.axis_names, self.hcg.mesh.devices.shape))[self.axis]

    @property
    def world_size(self):
        return self.nranks

    def __repr__(self):
        return f"Group(axis={self.axis}, nranks={self.nranks})"


_custom_groups: dict[int, Group] = {}


def _resolve_group(group) -> Group:
    hcg = get_hybrid_communicate_group()
    if hcg is None:
        raise RuntimeError("call paddle_tpu.distributed.init_parallel_env() or fleet.init() first")
    if group is None:
        # default group = the full data-parallel axis if >1, else first >1 axis
        for axis in hcg.mesh.axis_names:
            if dict(zip(hcg.mesh.axis_names, hcg.mesh.devices.shape))[axis] > 1:
                return Group(axis, hcg)
        return Group(hcg.mesh.axis_names[0], hcg)
    if isinstance(group, Group):
        return group
    if isinstance(group, str):
        return Group(group, hcg)
    raise TypeError(f"bad group {group!r}")


def new_group(ranks=None, axis=None, backend=None, timeout=None):
    """Reference new_group parity: here a group IS a mesh axis name."""
    g = _resolve_group(axis)
    _custom_groups[len(_custom_groups)] = g
    return g


def get_group(gid=0):
    return _custom_groups.get(gid)


def _v(x):
    return x._value if isinstance(x, Tensor) else jnp.asarray(x)


def _wrap_like(out, x):
    if isinstance(x, Tensor):
        x._value = out
        return x
    return Tensor._wrap(out)


def _axis_spec(arr_ndim, axis_name, shard_dim=0):
    spec = [None] * arr_ndim
    spec[shard_dim] = axis_name
    return P(*spec)


def shard_to_group(host_batches, group=None, shard_dim=0):
    """Place a list of per-rank numpy arrays as one array sharded over the
    group axis (test/debug helper: builds the reference's 'one tensor per
    rank' picture on the mesh)."""
    g = _resolve_group(group)
    stacked = np.concatenate([np.asarray(b) for b in host_batches], axis=shard_dim)
    sharding = NamedSharding(g.hcg.mesh, _axis_spec(stacked.ndim, g.axis, shard_dim))
    return Tensor._wrap(jax.device_put(stacked, sharding))


def unshard(t):
    return np.asarray(jax.device_get(_v(t)))


def _rank_of(g: Group) -> int:
    try:
        return int(g.hcg._coord(g.axis))
    except Exception:  # lint: allow-silent(no hcg topology; process index is the rank)
        return int(jax.process_index())


def _shard_mapped(g: Group, fn, *arrays, in_specs=None, out_specs=None,
                  op="collective"):
    mesh = g.hcg.mesh
    in_specs = in_specs if in_specs is not None else tuple(
        _axis_spec(a.ndim, g.axis) for a in arrays)
    out_specs = out_specs if out_specs is not None else in_specs[0]
    mapped = _shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )

    def invoke():
        # chaos site inside the guarded region, so injected delays/errors
        # exercise the watchdog exactly like a wedged transport would
        faults.inject(f"collective.{op}", axis=g.axis)
        return mapped(*arrays)

    nbytes = sum(int(getattr(a, "nbytes", 0)) for a in arrays)
    telemetry.record_event("collective.launch", op=op, axis=g.axis,
                           nranks=g.nranks, bytes=nbytes)
    _M_CALLS.labels(op=op).inc()
    _M_BYTES.labels(op=op).inc(nbytes)

    timeout = float(flag_value("FLAGS_collective_timeout_s") or 0.0)
    t0 = time.monotonic()
    # cluster heartbeat: when a RankPublisher is installed, every rank
    # publishes (op, seq#, entered/exited stamps) to the store — the
    # ClusterMonitor's straggler/desync/hang signal. One global load when
    # no publisher is configured.
    _cluster.collective_enter(op, axis=g.axis, nranks=g.nranks)
    try:
        if timeout <= 0:
            return invoke()
        return _guard_timeout(invoke, op, g, timeout)
    except CollectiveTimeoutError as e:
        # the postmortem artifact: the ring's tail holds this launch, the
        # fault (if injected) and everything leading up to the wedge
        _M_TIMEOUTS.labels(op=op).inc()
        telemetry.record_event("collective.timeout", op=op, axis=g.axis,
                               nranks=g.nranks, rank=_rank_of(g),
                               timeout_s=timeout)
        telemetry.dump(reason=f"collective timeout: {op}", error=e)
        # fleet-wide: ask EVERY rank for its flight dump + stacks, so the
        # postmortem answers "who hung", not just "I timed out"
        _cluster.trigger_postmortem(f"collective timeout: {op} "
                                    f"(rank {_rank_of(g)})")
        raise
    finally:
        _cluster.collective_exit(op)
        dt = time.monotonic() - t0
        _M_SECONDS.labels(op=op).observe(dt)
        # step-time attribution: when a StepTimeline step is open on this
        # thread (train loop / decode loop), this collective's wall time
        # lands in its "collective" phase — one TLS check when none is
        _perf.note_phase("collective", dt)


def _guard_timeout(invoke, op: str, g: Group, timeout: float):
    """Run the collective on a worker thread and bound the wait. A stuck
    collective (one rank dead, ICI wedge) otherwise hangs the host forever
    with no attribution; here it becomes a CollectiveTimeoutError naming
    op/group/rank. The worker thread cannot be killed — the caller is
    expected to tear the process down (elastic restart), not resume."""
    result: list = [None]
    error: list = [None]
    done = threading.Event()

    def target():
        try:
            result[0] = invoke()
        except BaseException as e:  # lint: allow-silent(error re-raised on the caller thread)
            error[0] = e
        finally:
            done.set()

    t = threading.Thread(target=target, daemon=True,
                         name=f"collective-{op}")
    t.start()
    if not done.wait(timeout):
        raise CollectiveTimeoutError(
            f"collective '{op}' over group axis '{g.axis}' "
            f"(nranks={g.nranks}, rank={_rank_of(g)}) did not complete "
            f"within {timeout}s — a peer is stuck or the interconnect is "
            f"wedged; the in-flight call cannot be cancelled")
    if error[0] is not None:
        raise error[0]
    return result[0]


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    g = _resolve_group(group)
    arr = _v(tensor)
    red = {
        ReduceOp.SUM: jax.lax.psum,
        ReduceOp.MAX: jax.lax.pmax,
        ReduceOp.MIN: jax.lax.pmin,
        ReduceOp.AVG: jax.lax.pmean,
        ReduceOp.PROD: lambda x, n: jnp.exp(jax.lax.psum(jnp.log(x), n)),
    }[op]
    out = _shard_mapped(g, lambda x: red(x, g.axis), arr, op="all_reduce")
    return _wrap_like(out, tensor)


def all_gather(tensor_list, tensor=None, group=None, sync_op=True, axis=0):
    """Two call shapes, like the reference: all_gather(out_list, x) or
    all_gather(x) -> Tensor (concatenated along axis 0 per-rank shards)."""
    if tensor is None:
        tensor, tensor_list = tensor_list, None
    g = _resolve_group(group)
    arr = _v(tensor)
    n = g.nranks

    def body(x):
        return jax.lax.all_gather(x, g.axis, axis=0, tiled=False)

    spec_in = _axis_spec(arr.ndim, g.axis)
    # every rank holds the identical gathered stack -> replicated out spec
    out_spec = P(*([None] * (arr.ndim + 1)))
    out = _shard_mapped(g, body, arr, in_specs=(spec_in,), out_specs=out_spec,
                        op="all_gather")
    # out: [n, *local_shape] along leading axis
    got = jax.device_get(out)
    shards = [Tensor._wrap(jnp.asarray(got[i])) for i in range(n)]
    if tensor_list is not None:
        tensor_list.extend(shards)
        return tensor_list
    return Tensor._wrap(jnp.concatenate([s._value for s in shards], axis=axis))


def reduce_scatter(tensor, tensor_or_op=None, op=ReduceOp.SUM, group=None, sync_op=True):
    g = _resolve_group(group)
    arr = _v(tensor)

    def body(x):
        return jax.lax.psum_scatter(x, g.axis, scatter_dimension=0, tiled=True)

    out = _shard_mapped(g, body, arr, op="reduce_scatter")
    return _wrap_like(out, tensor)


def broadcast(tensor, src=0, group=None, sync_op=True):
    g = _resolve_group(group)
    arr = _v(tensor)

    def body(x):
        # take src rank's shard everywhere
        idx = jax.lax.axis_index(g.axis)
        full = jax.lax.all_gather(x, g.axis, axis=0, tiled=False)
        return full[src]

    out = _shard_mapped(g, body, arr, op="broadcast")
    return _wrap_like(out, tensor)


def all_to_all(out_tensor_list, in_tensor_list=None, group=None, sync_op=True):
    """Reference alltoall (rank i sends in_tensor_list[j] to rank j).

    Single-tensor form (used by MoE dispatch inside jit): the group-sharded
    tensor's local [n*k, ...] rows are exchanged with a REAL
    ``lax.all_to_all``. List form is a host-side emulation for the
    single-controller eager API: with every rank holding the same list, rank
    r receives in_list[r] from each sender, so each output entry is the
    group-sharded concat of the input list."""
    g = _resolve_group(group)
    if in_tensor_list is None:
        # single-tensor form: local rows [n*k, ...] exchanged across ranks
        arr = _v(out_tensor_list)

        def body(x):
            xs = x.reshape(g.nranks, -1, *x.shape[1:])
            swapped = jax.lax.all_to_all(xs, g.axis, 0, 0, tiled=False)
            return swapped.reshape(-1, *x.shape[1:])

        out = _shard_mapped(g, body, arr, op="all_to_all")
        return Tensor._wrap(out)
    n = g.nranks
    if len(in_tensor_list) != n:
        raise ValueError(f"in_tensor_list must have {n} entries, got {len(in_tensor_list)}")
    gathered = shard_to_group([np.asarray(_v(t)) for t in in_tensor_list], group=g)
    got = jax.device_get(gathered._value)
    per = got.shape[0] // n
    out_tensor_list.clear()
    out_tensor_list.extend(
        Tensor._wrap(jnp.asarray(got[i * per:(i + 1) * per])) for i in range(n))
    return out_tensor_list


alltoall = all_to_all


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    """Rank-asymmetric reduce (reference
    /root/reference/python/paddle/distributed/communication/reduce.py):
    rank `dst` receives the reduction; every OTHER rank's result is its own
    input unchanged (the reference leaves non-dst outputs untouched)."""
    g = _resolve_group(group)
    arr = _v(tensor)
    reducer = {ReduceOp.SUM: jax.lax.psum, ReduceOp.MAX: jax.lax.pmax,
               ReduceOp.MIN: jax.lax.pmin, ReduceOp.AVG: jax.lax.pmean}.get(op)
    if reducer is None and op != ReduceOp.PROD:
        raise ValueError(f"unsupported reduce op: {op!r}")
    if reducer is None:  # PROD: psum of logs is lossy; gather
        def body(x):
            xs = jax.lax.all_gather(x, g.axis)
            red = jnp.prod(xs, axis=0)
            me = jax.lax.axis_index(g.axis)
            return jnp.where(me == dst, red, x)
    else:
        def body(x):
            red = reducer(x, g.axis)
            me = jax.lax.axis_index(g.axis)
            return jnp.where(me == dst, red, x)

    return _wrap_like(_shard_mapped(g, body, arr, op="reduce"), tensor)


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    """Scatter: rank r receives entry r of rank `src`'s tensor_list
    (reference /root/reference/python/paddle/distributed/communication/
    scatter.py). Single-controller NOTE, loudly: under this emulation every
    rank shares the controller's ``tensor_list`` — it IS src's list by
    construction, so the rank-asymmetric "other ranks' lists are ignored"
    clause is vacuously satisfied rather than exercised; the divergent-list
    case only exists in multi-process execution (jax.distributed), where
    each process passes its own list and only src's reaches the mesh. The
    data movement itself is real: the stacked list is laid out group-sharded
    so rank r's shard is exactly entry r."""
    g = _resolve_group(group)
    n = g.nranks
    if tensor_list is None:
        return _wrap_like(jnp.asarray(_v(tensor)), tensor)
    if len(tensor_list) != n:
        raise ValueError(
            f"scatter needs one entry per rank ({n}), got {len(tensor_list)}")
    stacked = np.stack([np.asarray(jax.device_get(_v(t)))
                        for t in tensor_list], axis=0)
    flat = stacked.reshape(n * stacked.shape[1] if stacked.ndim > 1 else n,
                           *stacked.shape[2:])
    sharding = NamedSharding(g.hcg.mesh, _axis_spec(flat.ndim, g.axis, 0))
    # reference mutates `tensor` in place; preserve that contract
    return _wrap_like(jax.device_put(flat, sharding), tensor)


def barrier(group=None):
    """Block until every process reaches the barrier (reference
    paddle.distributed.barrier). Single-process: device-queue drain only."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices("paddle_tpu_barrier")
    else:
        jax.block_until_ready(jnp.zeros(()))
    return None


def ppermute(tensor, perm, group=None):
    """Raw ppermute over the group axis (the p2p primitive under pipeline)."""
    g = _resolve_group(group)
    arr = _v(tensor)

    def body(x):
        return jax.lax.ppermute(x, g.axis, perm)

    out = _shard_mapped(g, body, arr, op="ppermute")
    return Tensor._wrap(out)


def send(tensor, dst=0, group=None, sync_op=True):
    """Point-to-point send ≈ ppermute src→dst (reference send_v2/p2p).
    Eager debugging only; pipeline uses ppermute inside the jitted schedule."""
    g = _resolve_group(group)
    src = g.hcg._coord(g.axis)
    return ppermute(tensor, [(src, dst)], group=g)


def recv(tensor, src=0, group=None, sync_op=True):
    g = _resolve_group(group)
    dst = g.hcg._coord(g.axis)
    out = ppermute(tensor, [(src, dst)], group=g)
    return _wrap_like(_v(out), tensor)
