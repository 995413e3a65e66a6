"""Interleaved virtual-stage pipeline (reference
PipelineParallelWithInterleave, pipeline_parallel.py:807): each device hosts
vpp non-adjacent chunks. Parity target: identical math to applying all
L = n*vpp chunks sequentially."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.distributed.mesh import build_mesh
from paddle_tpu.distributed.pipeline import (
    interleave_stage_params, spmd_pipeline_interleaved, stack_stage_params,
)

pytestmark = pytest.mark.usefixtures("uninstall_mesh")


def _chunk_fn(params, h):
    return jnp.tanh(h @ params["w"] + params["b"])


def _setup(n_stages=2, vpp=2, M=4, mb=4, d=8, seed=0):
    rng = np.random.RandomState(seed)
    L = n_stages * vpp
    per_stage = [
        {"w": rng.randn(d, d).astype(np.float32) * 0.3,
         "b": rng.randn(d).astype(np.float32) * 0.1}
        for _ in range(L)
    ]
    x = rng.randn(M, mb, d).astype(np.float32)
    stacked = stack_stage_params(per_stage)  # [L, ...]
    return per_stage, stacked, x


def _sequential(per_stage, x):
    h = x
    for p in per_stage:
        h = np.asarray(jnp.tanh(h @ p["w"] + p["b"]))
    return h


class TestInterleaved:
    def test_matches_sequential(self):
        per_stage, stacked, x = _setup()
        mesh = build_mesh(degrees={"pp": 2, "dp": 2, "mp": 2})
        inter = interleave_stage_params(stacked, n_stages=2)  # [n, vpp, ...]
        out = spmd_pipeline_interleaved(
            _chunk_fn, inter, x, mesh, n_stages=2, vpp=2)
        want = _sequential(per_stage, x)
        np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4, atol=1e-5)

    def test_param_layout(self):
        _, stacked, _ = _setup(n_stages=2, vpp=3)
        inter = interleave_stage_params(stacked, n_stages=2)
        # device d chunk c == logical stage c*n + d
        np.testing.assert_array_equal(
            np.asarray(inter["w"][0, 1]), np.asarray(stacked["w"][2]))
        np.testing.assert_array_equal(
            np.asarray(inter["w"][1, 2]), np.asarray(stacked["w"][5]))

    def test_gradients_match_sequential(self):
        per_stage, stacked, x = _setup(M=3, mb=2)
        mesh = build_mesh(degrees={"pp": 2, "dp": 2, "mp": 2})

        def loss_inter(params_L):
            inter = interleave_stage_params(params_L, n_stages=2)
            out = spmd_pipeline_interleaved(
                _chunk_fn, inter, x, mesh, n_stages=2, vpp=2, remat=False)
            return jnp.sum(out * out)

        def loss_seq(params_L):
            h = x
            for i in range(4):
                p = jax.tree_util.tree_map(lambda a: a[i], params_L)
                h = _chunk_fn(p, h)
            return jnp.sum(h * h)

        g_int = jax.grad(loss_inter)(stacked)
        g_seq = jax.grad(loss_seq)(stacked)
        for k in g_int:
            np.testing.assert_allclose(np.asarray(g_int[k]),
                                       np.asarray(g_seq[k]),
                                       rtol=1e-3, atol=1e-5)

    def test_gradients_with_remat(self):
        """remat=True (the default; jax.checkpoint inside scan-in-scan +
        ppermute) must produce the same grads as remat=False."""
        per_stage, stacked, x = _setup(M=3, mb=2)
        mesh = build_mesh(degrees={"pp": 2, "dp": 2, "mp": 2})

        def loss(params_L, remat):
            inter = interleave_stage_params(params_L, n_stages=2)
            out = spmd_pipeline_interleaved(
                _chunk_fn, inter, x, mesh, n_stages=2, vpp=2, remat=remat)
            return jnp.sum(out * out)

        g_remat = jax.grad(lambda p: loss(p, True))(stacked)
        g_plain = jax.grad(lambda p: loss(p, False))(stacked)
        for k in g_remat:
            np.testing.assert_allclose(np.asarray(g_remat[k]),
                                       np.asarray(g_plain[k]),
                                       rtol=1e-4, atol=1e-6)

    def test_llama_trainer_interleaved_matches_fthenb(self):
        """pp_schedule='interleaved' on the Llama trainer is the same math as
        fill-drain, re-laid-out over virtual chunks — losses must match."""
        from paddle_tpu.models import llama_tiny
        from paddle_tpu.models.llama_pipeline import LlamaPipelineTrainer
        from paddle_tpu.optimizer import AdamW

        def losses(schedule):
            mesh = build_mesh(degrees={"pp": 2, "dp": 2, "mp": 2})
            cfg = llama_tiny(vocab=64, hidden=32, layers=4, heads=4,
                             kv_heads=2, inter=64, seq=32)
            trainer = LlamaPipelineTrainer(
                cfg, mesh, AdamW(learning_rate=1e-2), n_micro=4,
                zero_stage=2, seed=0, pp_schedule=schedule, vpp=2)
            rng = np.random.RandomState(0)
            out = []
            for _ in range(2):
                x = rng.randint(0, 64, (8, 16)).astype(np.int64)
                y = rng.randint(0, 64, (8, 16)).astype(np.int64)
                out.append(float(np.asarray(trainer.step(x, y))))
            return out

        np.testing.assert_allclose(losses("interleaved"), losses("fthenb"),
                                   rtol=2e-4, atol=2e-5)

    def test_deeper_ring_pp4_vpp2(self):
        per_stage, stacked, x = _setup(n_stages=4, vpp=2, M=6)
        mesh = build_mesh(degrees={"pp": 4, "dp": 2})
        inter = interleave_stage_params(stacked, n_stages=4)
        out = spmd_pipeline_interleaved(
            _chunk_fn, inter, x, mesh, n_stages=4, vpp=2)
        np.testing.assert_allclose(np.asarray(out), _sequential(per_stage, x),
                                   rtol=1e-4, atol=1e-5)
