"""paddle.distributed.spawn — the notebook/single-file entry to
multi-process training (reference
/root/reference/python/paddle/distributed/spawn.py:428).

Each spawned process gets the same env contract the launch CLI sets
(PADDLE_TPU_COORDINATOR / NUM_PROCESSES / PROCESS_ID plus the reference's
PADDLE_TRAINER_* names); ``func`` then calls
``paddle.distributed.init_parallel_env()`` which runs
``jax.distributed.initialize`` — after that every process sees the global
device pool and XLA collectives span processes (ICI/DCN on real TPU pods,
gloo on CPU test meshes).
"""
from __future__ import annotations

import contextlib
import multiprocessing as mp
import os
import socket
import sys
import traceback

__all__ = ["spawn", "MultiprocessContext"]


@contextlib.contextmanager
def _temp_env(env):
    """Apply env in the PARENT around Process.start(): the spawned child
    interpreter inherits it from exec time, so platform/plugin selection
    (JAX_PLATFORMS, XLA_FLAGS, PYTHONPATH) is right BEFORE the child's
    first import — os.environ.update inside the child would be too late
    for anything read at interpreter/site startup."""
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _worker(func, rank, args, env, return_queue, error_queue):
    os.environ.update(env)
    try:
        ret = func(*args)
        return_queue.put((rank, ret))
    except KeyboardInterrupt:
        pass
    except Exception:
        error_queue.put((rank, traceback.format_exc()))
        sys.exit(1)


class MultiprocessContext:
    """Handle over the spawned fleet (reference MultiprocessContext:
    join(timeout) reaps processes and re-raises the first child failure)."""

    def __init__(self, processes, return_queue, error_queue):
        self.processes = processes
        self._return_queue = return_queue
        self._error_queue = error_queue
        self.returns: dict[int, object] = {}

    def join(self, timeout=None):
        for p in self.processes:
            p.join(timeout)
        while not self._return_queue.empty():
            rank, ret = self._return_queue.get_nowait()
            self.returns[rank] = ret
        if not self._error_queue.empty():
            rank, tb = self._error_queue.get()
            for p in self.processes:
                if p.is_alive():
                    p.terminate()
            raise RuntimeError(
                f"spawned process {rank} failed:\n{tb}")
        alive = [p for p in self.processes if p.is_alive()]
        if timeout is not None and alive:
            return False
        for p in self.processes:
            if p.exitcode not in (0, None):
                raise RuntimeError(
                    f"spawned process {p.name} exited with {p.exitcode}")
        return True


def spawn(func, args=(), nprocs=-1, join=True, daemon=False, **options):
    """Start ``nprocs`` processes running ``func(*args)`` for collective
    training. Options: start_method ('spawn' default — the CUDA-safe choice
    in the reference; JAX parents are multithreaded so fork carries the same
    hazard), env (dict of extra child env vars, e.g. JAX_PLATFORMS/XLA_FLAGS
    for CPU test meshes), ips / coordinator for multi-host.

    ``nprocs=-1`` means ``$PADDLE_TPU_NUM_DEVICES`` or one process: on one
    host a single process drives every local chip. The parent never asks
    JAX for a device count — a process that has initialised a backend holds
    the chips its children need."""
    if nprocs == -1:
        nprocs = int(os.environ.get("PADDLE_TPU_NUM_DEVICES", "0")) or 1
    start_method = options.get("start_method", "spawn")
    ctx = mp.get_context(start_method)
    return_queue = ctx.Queue()
    error_queue = ctx.Queue()

    coordinator = options.get(
        "coordinator", f"127.0.0.1:{_free_port()}")
    base_env = {
        "PADDLE_TPU_COORDINATOR": coordinator,
        "PADDLE_TPU_NUM_PROCESSES": str(nprocs),
        "PADDLE_TRAINERS_NUM": str(nprocs),
        "PADDLE_DISTRI_BACKEND": str(options.get("backend", "auto")),
    }
    base_env.update(options.get("env", {}))

    processes = []
    for rank in range(nprocs):
        env = dict(base_env)
        env["PADDLE_TPU_PROCESS_ID"] = str(rank)
        env["PADDLE_TRAINER_ID"] = str(rank)
        p = ctx.Process(
            target=_worker,
            args=(func, rank, tuple(args), env, return_queue, error_queue),
            daemon=daemon, name=f"paddle-spawn-{rank}")
        with _temp_env(env):
            p.start()
        processes.append(p)

    context = MultiprocessContext(processes, return_queue, error_queue)
    if join:
        context.join()
    return context
