"""Multiprocess DataLoader workers + shared-memory ring (VERDICT r3
missing #4 / next-round #6). Reference:
/root/reference/python/paddle/io/dataloader/worker.py:1 (per-worker
processes), dataloader_iter.py (ordered multi-process acquisition),
use_shared_memory transport.

NOTE on scaling: this sandbox exposes ONE cpu core (os.sched_getaffinity),
so a >2x wall-clock scaling assertion is physically impossible here; these
tests prove process-ness, ordering, worker_info, error propagation and
shared-memory transport instead. The scaling curve on a multi-core host
has not been measured.
"""
import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.io import DataLoader, Dataset, IterableDataset, get_worker_info


class SquareDataset(Dataset):
    def __len__(self):
        return 23

    def __getitem__(self, i):
        return np.float32(i), np.int64(i * i)


class TransformDataset(Dataset):
    """CPU-heavy python transform: the workload process workers exist for."""

    def __len__(self):
        return 12

    def __getitem__(self, i):
        rng = np.random.RandomState(i)
        x = rng.rand(64).astype(np.float32)
        for _ in range(20):  # pure-python loop: GIL-bound in threads
            x = np.tanh(x) + 0.01 * i
        return x, np.int64(i)


class PidDataset(Dataset):
    def __len__(self):
        return 8

    def __getitem__(self, i):
        wi = get_worker_info()
        return (np.int64(os.getpid()),
                np.int64(-1 if wi is None else wi.id),
                np.int64(i))


class BadDataset(Dataset):
    def __len__(self):
        return 8

    def __getitem__(self, i):
        if i == 5:
            raise ValueError("poisoned sample 5")
        return np.float32(i)


class CountStream(IterableDataset):
    def __iter__(self):
        wi = get_worker_info()
        wid = 0 if wi is None else wi.id
        for k in range(6):
            yield np.int64(wid * 100 + k)


class TestProcessWorkers:
    def test_content_and_order_match_inline(self):
        inline = list(DataLoader(SquareDataset(), batch_size=4,
                                 num_workers=0, use_buffer_reader=False))
        procs = list(DataLoader(SquareDataset(), batch_size=4,
                                num_workers=3))
        assert len(procs) == len(inline)
        for (a0, a1), (b0, b1) in zip(inline, procs):
            np.testing.assert_array_equal(a0.numpy(), b0.numpy())
            np.testing.assert_array_equal(a1.numpy(), b1.numpy())

    def test_workers_are_real_processes_with_worker_info(self):
        dl = DataLoader(PidDataset(), batch_size=2, num_workers=2)
        pids, wids = set(), set()
        for pid_t, wid_t, _ in dl:
            pids.update(int(p) for p in pid_t.numpy())
            wids.update(int(w) for w in wid_t.numpy())
        assert os.getpid() not in pids, "samples were produced in-parent"
        assert len(pids) == 2, f"expected 2 worker processes, saw {pids}"
        assert wids == {0, 1}, f"worker_info ids wrong: {wids}"

    def test_transform_pipeline_correct(self):
        inline = list(DataLoader(TransformDataset(), batch_size=3,
                                 num_workers=0, use_buffer_reader=False))
        procs = list(DataLoader(TransformDataset(), batch_size=3,
                                num_workers=4))
        for (a0, a1), (b0, b1) in zip(inline, procs):
            np.testing.assert_allclose(a0.numpy(), b0.numpy(), rtol=1e-6)
            np.testing.assert_array_equal(a1.numpy(), b1.numpy())

    def test_worker_error_propagates(self):
        dl = DataLoader(BadDataset(), batch_size=4, num_workers=2)
        with pytest.raises(RuntimeError, match="poisoned sample 5"):
            list(dl)

    def test_worker_init_fn_runs_in_worker(self):
        calls = []

        def init(wid):
            # runs in the CHILD; mutate env so the dataset can see it
            os.environ["_PDTPU_TEST_WID"] = str(wid)

        class EnvDataset(Dataset):
            def __len__(self):
                return 4

            def __getitem__(self, i):
                return np.int64(int(os.environ.get("_PDTPU_TEST_WID", -1)))

        dl = DataLoader(EnvDataset(), batch_size=2, num_workers=2,
                        worker_init_fn=init)
        seen = set()
        for b in dl:
            seen.update(int(v) for v in b.numpy())
        assert seen <= {0, 1} and seen, f"init fn not seen in workers: {seen}"
        assert "_PDTPU_TEST_WID" not in os.environ  # parent untouched

    def test_iterable_dataset_shards_by_worker_info(self):
        dl = DataLoader(CountStream(), batch_size=3, num_workers=2)
        vals = sorted(int(v) for b in dl for v in b.numpy())
        # each worker streams its own copy tagged by worker id (reference
        # semantics: sharding is the dataset's job via get_worker_info)
        assert vals == sorted([w * 100 + k for w in (0, 1) for k in range(6)])

    def test_custom_collate_structure_roundtrip(self):
        def collate(batch):
            xs = np.stack([b[0] for b in batch])
            return {"x": xs, "meta": [int(b[1]) for b in batch],
                    "pair": (xs.sum(), "tag")}

        dl = DataLoader(SquareDataset(), batch_size=4, num_workers=2,
                        collate_fn=collate, drop_last=True)
        out = list(dl)
        assert len(out) == 5
        first = out[0]
        assert isinstance(first["x"], np.ndarray)  # custom collate: raw np
        assert first["meta"] == [0, 1, 4, 9]
        assert first["pair"][1] == "tag"

    def test_large_batch_grows_ring_slot(self):
        class Big(Dataset):
            def __len__(self):
                return 4

            def __getitem__(self, i):
                # ~2MB per sample: exceeds the 1MB initial slot size
                return np.full((512, 1024), i, np.float32)

        dl = DataLoader(Big(), batch_size=2, num_workers=2)
        shapes = [b.shape for b in dl]
        assert shapes == [[2, 512, 1024], [2, 512, 1024]]

    def test_persistent_workers_survive_epochs(self):
        dl = DataLoader(PidDataset(), batch_size=2, num_workers=2,
                        persistent_workers=True)
        pids_by_epoch = []
        for _ in range(3):
            pids = set()
            for pid_t, _, _ in dl:
                pids.update(int(p) for p in pid_t.numpy())
            pids_by_epoch.append(pids)
        # same worker processes across all 3 epochs: no per-epoch re-fork
        assert pids_by_epoch[0] == pids_by_epoch[1] == pids_by_epoch[2]
        assert len(pids_by_epoch[0]) == 2
        dl._mp_iter.close()

    def test_worker_timeout_raises_clearly(self):
        class Slow(Dataset):
            def __len__(self):
                return 4

            def __getitem__(self, i):
                if i == 2:
                    time.sleep(30)
                return np.float32(i)

        dl = DataLoader(Slow(), batch_size=2, num_workers=2, timeout=2)
        with pytest.raises(RuntimeError, match="timed out"):
            list(dl)

    def test_accelerator_tensor_in_worker_raises(self):
        # host-backed tensors are allowed; the guard targets device buffers,
        # which we can't create on the CPU test platform — so assert the
        # host path works and the guard function rejects a fake device
        from paddle_tpu.io.worker import _tensor_to_np

        class TensorDataset(Dataset):
            def __len__(self):
                return 4

            def __getitem__(self, i):
                return paddle.to_tensor(np.float32(i))

        out = list(DataLoader(TensorDataset(), batch_size=2, num_workers=2))
        assert len(out) == 2

        class FakeDev:
            platform = "tpu"

        class FakeVal:
            def devices(self):
                return {FakeDev()}

        class FakeTensor:
            _value = FakeVal()

        with pytest.raises(RuntimeError, match="accelerator-backed"):
            _tensor_to_np(FakeTensor())
