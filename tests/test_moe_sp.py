"""MoE routing + expert-parallel and ring/Ulysses sequence parallelism.

VERDICT r1 #3: these shipped in round 1 with zero tests. Reference shapes:
MoE — /root/reference/python/paddle/incubate/distributed/models/moe/
moe_layer.py:263 and gates; SP is beyond-reference (SURVEY §5.7).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.distributed.mesh import (
    HybridCommunicateGroup, build_mesh, set_hybrid_communicate_group,
)
from paddle_tpu.distributed.moe import MoELayer, top1_gating, top2_gating
from paddle_tpu.distributed.sequence_parallel import (
    ring_attention, ulysses_attention,
)
from paddle_tpu.nn.functional.attention import sdpa_ref
from paddle_tpu.nn.layer import functional_call, functional_state


# ---------------------------------------------------------------------------
# sequence parallel
# ---------------------------------------------------------------------------

def _qkv(rng, B=2, S=32, H=8, D=16, dtype=np.float32):
    q = rng.standard_normal((B, S, H, D)).astype(dtype)
    k = rng.standard_normal((B, S, H, D)).astype(dtype)
    v = rng.standard_normal((B, S, H, D)).astype(dtype)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_full_attention(self, causal):
        mesh = build_mesh(degrees={"sep": 4})
        rng = np.random.default_rng(0)
        q, k, v = _qkv(rng)
        out = ring_attention(q, k, v, mesh=mesh, causal=causal)
        ref = sdpa_ref(q, k, v, is_causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("causal", [True, False])
    def test_grads_match(self, causal):
        mesh = build_mesh(degrees={"sep": 4})
        rng = np.random.default_rng(1)
        q, k, v = _qkv(rng, B=1, S=16, H=4, D=8)

        def loss_ring(q, k, v):
            return jnp.sum(ring_attention(q, k, v, mesh=mesh, causal=causal) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(sdpa_ref(q, k, v, is_causal=causal) ** 2)

        gr = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        gf = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gr, gf):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=2e-4)

    def test_sep1_falls_back(self):
        mesh = build_mesh(degrees={"sep": 1})
        rng = np.random.default_rng(2)
        q, k, v = _qkv(rng, S=8)
        out = ring_attention(q, k, v, mesh=mesh, causal=True)
        ref = sdpa_ref(q, k, v, is_causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


class TestUlyssesAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_full_attention(self, causal):
        mesh = build_mesh(degrees={"sep": 4})
        rng = np.random.default_rng(3)
        q, k, v = _qkv(rng)  # H=8 divisible by sep=4
        out = ulysses_attention(q, k, v, mesh=mesh, causal=causal)
        ref = sdpa_ref(q, k, v, is_causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_grads_match(self):
        mesh = build_mesh(degrees={"sep": 4})
        rng = np.random.default_rng(4)
        q, k, v = _qkv(rng, B=1, S=16, H=4, D=8)

        def loss_u(q, k, v):
            return jnp.sum(ulysses_attention(q, k, v, mesh=mesh, causal=True) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(sdpa_ref(q, k, v, is_causal=True) ** 2)

        gu = jax.grad(loss_u, argnums=(0, 1, 2))(q, k, v)
        gf = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gu, gf):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

class TestGating:
    def test_top2_mass_conservation(self):
        rng = np.random.default_rng(5)
        logits = jnp.asarray(rng.standard_normal((32, 4)).astype(np.float32))
        dispatch, combine, aux = top2_gating(logits, capacity=32)
        # ample capacity: every token keeps both choices, weights sum to 1
        np.testing.assert_allclose(
            np.asarray(combine.sum(axis=(1, 2))), 1.0, atol=1e-5)
        # each (expert, slot) holds at most one token
        assert np.all(np.asarray(dispatch.sum(axis=0)) <= 1.0 + 1e-6)
        assert np.isfinite(float(aux))

    def test_top1_capacity_overflow_drops_tokens(self):
        rng = np.random.default_rng(6)
        logits = jnp.asarray(rng.standard_normal((32, 2)).astype(np.float32))
        dispatch, combine, aux = top1_gating(logits, capacity=4)
        per_expert = np.asarray(dispatch.sum(axis=(0, 2)))
        assert np.all(per_expert <= 4 + 1e-6)  # capacity respected
        kept = np.asarray(dispatch.sum(axis=(1, 2)))
        assert kept.min() == 0.0  # 32 tokens into 2x4 slots => drops
        # dropped tokens carry zero combine weight
        dropped = kept < 0.5
        np.testing.assert_allclose(
            np.asarray(combine.sum(axis=(1, 2)))[dropped], 0.0, atol=1e-6)

    def test_top1_uniform_aux_loss_is_one(self):
        # uniform router: density_proxy = 1/E, aux = E * sum(density/E) = 1
        logits = jnp.zeros((16, 4), jnp.float32)
        _, _, aux = top1_gating(logits, capacity=16)
        np.testing.assert_allclose(float(aux), 1.0, atol=1e-6)


class TestMoELayer:
    def _ref_forward(self, layer, x):
        """Dense per-token reference for top-1 routing with ample capacity."""
        gw = layer.gate_weight.numpy()
        w1, b1 = layer.w1.numpy(), layer.b1.numpy()
        w2, b2 = layer.w2.numpy(), layer.b2.numpy()
        xf = x.reshape(-1, x.shape[-1])
        logits = xf @ gw
        probs = np.exp(logits - logits.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        out = np.zeros_like(xf)
        for t in range(xf.shape[0]):
            e = int(np.argmax(probs[t]))
            h = xf[t] @ w1[e] + b1[e][0]
            h = np.asarray(jax.nn.gelu(jnp.asarray(h)))
            out[t] = (h @ w2[e] + b2[e][0]) * probs[t, e]
        return out.reshape(x.shape)

    def test_forward_matches_dense_reference(self):
        paddle.seed(0)
        layer = MoELayer(16, 32, num_experts=4, gate="switch",
                         capacity_factor=8.0)
        rng = np.random.default_rng(7)
        x = rng.standard_normal((2, 6, 16)).astype(np.float32)
        out = layer(paddle.to_tensor(x))
        aux = layer.aux_loss
        ref = self._ref_forward(layer, x)
        np.testing.assert_allclose(out.numpy(), ref, atol=1e-4, rtol=1e-4)
        assert np.isfinite(float(aux.numpy()))

    def test_backward_reaches_experts_and_gate(self):
        paddle.seed(1)
        layer = MoELayer(8, 16, num_experts=2, gate="gshard")
        rng = np.random.default_rng(8)
        x = paddle.to_tensor(rng.standard_normal((4, 8)).astype(np.float32))
        out = layer(x)
        (out.sum() + layer.aux_loss).backward()
        for p in (layer.gate_weight, layer.w1, layer.w2):
            assert p._grad is not None
            assert float(np.abs(np.asarray(p._grad)).max()) > 0

    def test_expert_parallel_matches_single_device(self):
        paddle.seed(2)
        layer = MoELayer(16, 32, num_experts=4, gate="gshard",
                         capacity_factor=8.0)
        rng = np.random.default_rng(9)
        x = rng.standard_normal((8, 16)).astype(np.float32)
        out_eager = layer(paddle.to_tensor(x))

        mesh = build_mesh(degrees={"ep": 4})
        set_hybrid_communicate_group(HybridCommunicateGroup(None, mesh))
        try:
            params, bufs = functional_state(layer)
            named = dict(layer.named_parameters())
            sharded = {}
            for n, v in params.items():
                spec = named[n].sharding_spec
                s = NamedSharding(mesh, spec if spec is not None else P())
                sharded[n] = jax.device_put(v, s)

            @jax.jit
            def run(p, xv):
                out, _ = functional_call(layer, p, bufs, xv)
                return out

            out_ep = run(sharded, jnp.asarray(x))
            np.testing.assert_allclose(np.asarray(out_ep), out_eager.numpy(),
                                       atol=1e-4, rtol=1e-4)
        finally:
            set_hybrid_communicate_group(None)
