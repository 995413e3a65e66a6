"""Pipeline-parallel trainer with the Pallas flash-attention kernel enabled.

Regression for the round-1 multi-chip gate failure: the pallas_call out_shapes
carried no vma, so flash attention could not trace inside the check_vma=True
pp shard_map at all (on any backend). Here the kernel runs in interpret mode
on the 8-device CPU mesh — the analogue of the reference's fake custom_cpu
plugin CI (/root/reference/test/custom_runtime/test_custom_cpu_plugin.py:23).
"""
import numpy as np
import pytest

import jax

from paddle_tpu import kernels
from paddle_tpu.distributed.mesh import build_mesh
from paddle_tpu.models import llama_tiny
from paddle_tpu.models.llama_pipeline import LlamaPipelineTrainer
from paddle_tpu.optimizer import AdamW

pytestmark = pytest.mark.usefixtures("uninstall_mesh")


def _run_step(use_pallas: bool, seed=0):
    kernels.set_use_pallas(use_pallas)
    try:
        mesh = build_mesh(degrees={"pp": 2, "dp": 2, "mp": 2})
        cfg = llama_tiny(vocab=64, hidden=32, layers=4, heads=4, kv_heads=2,
                         inter=64, seq=32)
        trainer = LlamaPipelineTrainer(
            cfg, mesh, AdamW(learning_rate=1e-3), n_micro=4, zero_stage=2,
            seed=seed)
        rng = np.random.RandomState(seed)
        x = rng.randint(0, 64, (8, 16)).astype(np.int64)
        y = rng.randint(0, 64, (8, 16)).astype(np.int64)
        loss = trainer.step(x, y)
        jax.block_until_ready(loss)
        return float(np.asarray(loss))
    finally:
        kernels.set_use_pallas(None)


def test_pipeline_trainer_with_pallas_flash_attention():
    loss = _run_step(use_pallas=True)
    assert np.isfinite(loss)


def test_pipeline_pallas_matches_xla_attention():
    # same init seed => same params; the two attention impls must agree
    loss_pallas = _run_step(use_pallas=True)
    loss_xla = _run_step(use_pallas=False)
    assert loss_pallas == pytest.approx(loss_xla, rel=1e-4)
