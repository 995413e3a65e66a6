"""Multiprocess DataLoader workers with a shared-memory ring.

TPU-native counterpart of the reference's multi-process loader
(/root/reference/python/paddle/io/dataloader/worker.py:1,
 dataloader_iter.py `_DataLoaderIterMultiProcess`, and the C++ shared-memory
tensor transport): ``num_workers>0`` forks worker PROCESSES (escaping the
GIL for python transform pipelines), each owning a ring of reusable
shared-memory slots. Workers collate batches into numpy arrays, write the
bytes into a free ring slot, and send (skeleton, array specs) through a
result queue; the parent re-assembles Tensors from the slot and returns the
slot to the worker's free-list — backpressure and zero pickling for the
array payload.

Fork-safety: workers NEVER touch jax — the default collate runs a
numpy-only twin (``_np_collate``), and Tensor leaves from custom collates
are unwrapped to numpy before transport. (A forked child driving the
parent's TPU client would be undefined behavior, same reason the
reference forbids CUDA in workers.)

Batch order is deterministic: batch i is assigned to worker ``i % W`` and
each worker preserves its own order, so the parent drains workers
round-robin — the reference's ordered reacquisition without the reorder
buffer.
"""
from __future__ import annotations

import multiprocessing as mp
import os
import traceback
from multiprocessing import shared_memory

import numpy as np

__all__ = ["MultiProcessLoaderIter"]


class _ArrRef:
    """Skeleton placeholder for an array leaf moved through shared memory."""

    __slots__ = ("idx", "kind")

    def __init__(self, idx, kind):
        self.idx = idx
        self.kind = kind  # "tensor" -> rewrap as Tensor in the parent


def _tensor_to_np(t):
    """Unwrap a Tensor in a WORKER process. Host(cpu)-backed values are a
    metadata-free numpy view; an accelerator-committed buffer would have to
    round-trip the parent's device client from a forked child — undefined
    behavior, so refuse loudly (the reference similarly forbids CUDA
    tensors in loader workers)."""
    v = t._value
    try:
        devs = {d.platform for d in v.devices()}
    except Exception:
        devs = {"cpu"}
    if devs - {"cpu"}:
        raise RuntimeError(
            "DataLoader worker received an accelerator-backed Tensor "
            f"(devices {sorted(devs)}); datasets/collate_fns used with "
            "num_workers>0 must return numpy arrays or host tensors")
    return np.asarray(v)


def _encode(obj, arrays):
    from ..core.tensor import Tensor

    if isinstance(obj, Tensor):
        arrays.append(_tensor_to_np(obj))
        return _ArrRef(len(arrays) - 1, "tensor")
    if isinstance(obj, np.ndarray):
        arrays.append(np.ascontiguousarray(obj))
        return _ArrRef(len(arrays) - 1, "ndarray")
    if isinstance(obj, tuple):
        return tuple(_encode(o, arrays) for o in obj)
    if isinstance(obj, list):
        return [_encode(o, arrays) for o in obj]
    if isinstance(obj, dict):
        return {k: _encode(v, arrays) for k, v in obj.items()}
    return obj


def _decode(obj, arrays):
    from ..core.tensor import Tensor

    if isinstance(obj, _ArrRef):
        arr = arrays[obj.idx]
        return Tensor(arr) if obj.kind == "tensor" else arr
    if isinstance(obj, tuple):
        return tuple(_decode(o, arrays) for o in obj)
    if isinstance(obj, list):
        return [_decode(o, arrays) for o in obj]
    if isinstance(obj, dict):
        return {k: _decode(v, arrays) for k, v in obj.items()}
    return obj


def _np_collate(batch):
    """Numpy-only twin of default_collate_fn (workers must not build
    Tensors: jax in a forked child would drive the parent's device client).
    Leaves are marked "tensor" so the parent rewraps them."""
    from ..core.tensor import Tensor

    sample = batch[0]
    if isinstance(sample, Tensor):
        return np.stack([_tensor_to_np(s) for s in batch])
    if isinstance(sample, np.ndarray):
        return np.stack(batch)
    if isinstance(sample, (int, np.integer)):
        return np.asarray(batch, np.int64)
    if isinstance(sample, (float, np.floating)):
        return np.asarray(batch, np.float32)
    if isinstance(sample, (tuple, list)):
        return [_np_collate(list(items)) for items in zip(*batch)]
    if isinstance(sample, dict):
        return {k: _np_collate([d[k] for d in batch]) for k in sample}
    return batch


def _mark_all_tensor(obj):
    """Skeleton post-pass for the default-collate path: every array leaf
    becomes a Tensor in the parent (default_collate_fn's contract)."""
    if isinstance(obj, _ArrRef):
        return _ArrRef(obj.idx, "tensor")
    if isinstance(obj, tuple):
        return tuple(_mark_all_tensor(o) for o in obj)
    if isinstance(obj, list):
        return [_mark_all_tensor(o) for o in obj]
    if isinstance(obj, dict):
        return {k: _mark_all_tensor(v) for k, v in obj.items()}
    return obj


class _Slot:
    """One reusable shared-memory segment; grows (unlink + recreate) when a
    batch outgrows it. The parent attaches by the name sent per batch, so
    regrowth is transparent."""

    def __init__(self, wid, idx, size=1 << 20):
        self.idx = idx
        self.gen = 0
        self.wid = wid
        self.shm = shared_memory.SharedMemory(
            create=True, size=size, name=self._name())

    def _name(self):
        return f"pdtpu_{os.getpid()}_{self.wid}_{self.idx}_{self.gen}"

    def ensure(self, nbytes):
        if self.shm.size >= nbytes:
            return
        self.shm.close()
        self.shm.unlink()
        self.gen += 1
        self.shm = shared_memory.SharedMemory(
            create=True, size=max(nbytes, 2 * self.shm.size),
            name=self._name())

    def write(self, arrays):
        specs = []
        off = 0
        total = sum(a.nbytes for a in arrays)
        self.ensure(total)
        for a in arrays:
            # write in place: one copy into the segment (tobytes() would
            # materialize a transient duplicate of every batch)
            dst = np.ndarray(a.shape, a.dtype, buffer=self.shm.buf,
                             offset=off)
            np.copyto(dst, a)
            specs.append((tuple(a.shape), a.dtype.str, off))
            off += a.nbytes
        return self.shm.name, specs

    def destroy(self):
        try:
            self.shm.close()
            self.shm.unlink()
        except Exception:
            pass


def _worker_loop(loader_state, wid, index_q, result_q, free_q, n_slots):
    """Worker process main: collate assigned batches into the slot ring."""
    (dataset, collate, use_np_collate, worker_init_fn, num_workers,
     iterable, batch_size, drop_last) = loader_state
    from . import _WorkerInfo
    import paddle_tpu.io as _io

    _io._worker_info = _WorkerInfo(id=wid, num_workers=num_workers,
                                   dataset=dataset)
    if worker_init_fn is not None:
        worker_init_fn(wid)
    slots = [_Slot(wid, i) for i in range(n_slots)]
    for s in slots:
        free_q.put(s.idx)

    def send(bid, data):
        arrays = []
        skeleton = _encode(data, arrays)
        if use_np_collate:
            skeleton = _mark_all_tensor(skeleton)
        slot_idx = free_q.get()  # backpressure: waits for the parent
        name, specs = slots[slot_idx].write(arrays)
        result_q.put(("ok", bid, slot_idx, name, skeleton, specs))

    try:
        if iterable:
            bid = 0
            batch = []
            for item in dataset:
                batch.append(item)
                if batch_size is not None and len(batch) == batch_size:
                    send(bid, collate(batch))
                    bid += 1
                    batch = []
            if batch and not drop_last:
                send(bid, collate(batch))
            result_q.put(("end", None, None, None, None, None))
        else:
            # epoch-framed protocol: (bid, idxs) work items, "epoch_end"
            # markers (worker echoes an "end" so the parent can frame
            # epochs — this is what makes persistent_workers possible),
            # None = shutdown
            while True:
                item = index_q.get()
                if item is None:
                    break
                if item == "epoch_end":
                    result_q.put(("end", None, None, None, None, None))
                    continue
                bid, idxs = item
                send(bid, collate([dataset[i] for i in idxs]))
    except Exception:
        result_q.put(("err", traceback.format_exc(), None, None, None, None))
    finally:
        # segments must outlive the last in-flight batch: wait until the
        # parent has returned every slot (it returns one per copied batch),
        # then unlink. A 10s cap covers an abandoning parent; terminated
        # workers leave cleanup to the resource tracker.
        reclaimed = 0
        try:
            while reclaimed < n_slots:
                free_q.get(timeout=10)
                reclaimed += 1
        except Exception:
            pass
        for s in slots:
            s.destroy()


class MultiProcessLoaderIter:
    """Parent-side iterator over a fleet of worker processes."""

    def __init__(self, loader):
        from . import default_collate_fn

        self._loader = loader
        self._W = loader.num_workers
        ctx = mp.get_context("fork")
        self._workers = []
        self._index_qs = []
        self._result_qs = []
        self._free_qs = []
        self._slot_names: dict[tuple[int, int], str] = {}
        use_np = loader.collate_fn is default_collate_fn
        collate = _np_collate if use_np else loader.collate_fn
        n_slots = max(2, loader.prefetch_factor)
        self._iterable = loader.iterable_mode

        self._persistent = (getattr(loader, "persistent_workers", False)
                            and not self._iterable)
        self._total = None

        state = (loader.dataset, collate, use_np,
                 getattr(loader, "worker_init_fn", None), self._W,
                 self._iterable, loader.batch_size, loader.drop_last)
        for w in range(self._W):
            iq = ctx.Queue()
            rq = ctx.Queue()
            fq = ctx.Queue()
            p = ctx.Process(
                target=_worker_loop,
                args=(state, w, iq, rq, fq, n_slots), daemon=True)
            p.start()
            self._workers.append(p)
            self._index_qs.append(iq)
            self._result_qs.append(rq)
            self._free_qs.append(fq)

    def _feed_epoch(self):
        """Assign this epoch's batches round-robin (deterministic global
        order) and close the epoch with per-worker markers. Re-listing the
        sampler each epoch keeps shuffle-per-epoch semantics."""
        batches = list(self._loader.batch_sampler)
        self._total = len(batches)
        for bid, idxs in enumerate(batches):
            self._index_qs[bid % self._W].put((bid, idxs))
        for iq in self._index_qs:
            iq.put("epoch_end")

    def alive(self):
        return bool(self._workers) and all(p.is_alive()
                                           for p in self._workers)

    @staticmethod
    def _read_segment(name, end):
        """Copy `end` bytes out of the named shared-memory segment. Linux
        exposes segments under /dev/shm (direct read avoids 3.12's
        resource-tracker double-registration on attach); other POSIX systems
        fall back to a SharedMemory attach with tracking suppressed."""
        try:
            with open(f"/dev/shm/{name}", "rb") as f:
                return f.read(end)
        except FileNotFoundError:
            from multiprocessing import shared_memory

            try:
                seg = shared_memory.SharedMemory(name=name, track=False)
            except TypeError:  # <3.13: no track kwarg; unregister manually
                seg = shared_memory.SharedMemory(name=name)
                try:
                    from multiprocessing import resource_tracker

                    resource_tracker.unregister(seg._name, "shared_memory")
                except Exception:
                    pass
            try:
                return bytes(seg.buf[:end])
            finally:
                seg.close()

    #: timeout=0 (paddle's "no timeout") maps to this cap instead of blocking
    #: forever: the fleet is fork-started from a multithreaded JAX parent, and
    #: a child that forked while another thread held a lock (malloc/numpy/
    #: logging) can wedge silently — a bounded get turns that hang into a
    #: diagnosable error.
    DEFAULT_READ_TIMEOUT = 600.0

    def _read_one(self, w):
        import queue as _queue

        timeout = (getattr(self._loader, "timeout", 0)
                   or self.DEFAULT_READ_TIMEOUT)
        try:
            msg = self._result_qs[w].get(timeout=timeout)
        except _queue.Empty:
            self.close()
            raise RuntimeError(
                f"DataLoader worker {w} timed out after {timeout}s (stuck "
                "__getitem__/collate_fn, or a fork-while-threaded deadlock "
                "— set DataLoader(timeout=...) to tune the cap)") from None
        kind = msg[0]
        if kind == "err":
            self.close()
            raise RuntimeError(
                f"DataLoader worker {w} failed:\n{msg[1]}")
        if kind == "end":
            return None
        _, bid, slot_idx, name, skeleton, specs = msg
        self._slot_names[(w, slot_idx)] = name
        # read the segment file directly instead of SharedMemory(name=...):
        # the parent copies the bytes out anyway, and 3.12's attach path
        # would register the segment with the shared resource tracker,
        # producing unlink-race warnings against the owning worker
        end = max((off + int(np.prod(shape or (1,))) * np.dtype(dt).itemsize)
                  for shape, dt, off in specs) if specs else 0
        raw = self._read_segment(name, end)
        arrays = []
        for shape, dtype, off in specs:
            n = int(np.prod(shape)) if shape else 1
            a = np.frombuffer(raw, dtype=np.dtype(dtype), count=n,
                              offset=off).reshape(shape).copy()
            arrays.append(a)
        self._free_qs[w].put(slot_idx)  # ring slot back to the worker
        return _decode(skeleton, arrays)

    def __iter__(self):
        completed = False
        try:
            if self._iterable:
                live = list(range(self._W))
                while live:
                    for w in list(live):
                        out = self._read_one(w)
                        if out is None:
                            live.remove(w)
                        else:
                            yield out
            else:
                self._feed_epoch()
                for bid in range(self._total):
                    out = self._read_one(bid % self._W)
                    if out is None:  # worker ended early: internal error
                        raise RuntimeError(
                            "DataLoader worker ended before its batches")
                    yield out
                # drain the per-worker epoch markers so the NEXT epoch's
                # reads start framed
                for w in range(self._W):
                    if self._read_one(w) is not None:
                        raise RuntimeError(
                            "DataLoader worker/epoch desynchronization")
                completed = True
        finally:
            # persistent workers survive a CLEANLY completed epoch; an
            # abandoned iteration leaves batches in flight, so the fleet is
            # torn down either way to avoid desync
            if not (self._persistent and completed):
                self.close()

    def close(self):
        for p, iq in zip(self._workers, self._index_qs):
            try:
                iq.put_nowait(None)
            except Exception:
                pass
        dirty = set()
        for w, p in enumerate(self._workers):
            p.join(timeout=2)
            if p.is_alive():
                p.terminate()
                dirty.add(w)
            elif p.exitcode not in (0, None):
                dirty.add(w)
        # a cleanly-exited worker unlinked its own slots; only sweep up
        # after terminated/crashed workers (double-unlink trips the
        # resource tracker's warnings)
        for (w, _), name in self._slot_names.items():
            if w not in dirty:
                continue
            try:
                shm = shared_memory.SharedMemory(name=name)
                shm.close()
                shm.unlink()
            except Exception:
                pass
        self._workers = []
