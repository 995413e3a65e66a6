"""Round-5 op-bench kernels (VERDICT r4 next #5): fused RMSNorm(+residual)
and streaming softmax-CE — interpret-mode parity vs the XLA compositions.
Neither kernel has been timed on the chip (ROADMAP Design 6); these tests
gate correctness only."""
import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu import kernels


class TestFusedRMSNorm:
    def _ref(self, x, r, w, eps=1e-6):
        s = x + r
        return s * jax.lax.rsqrt(jnp.mean(s * s, -1, keepdims=True) + eps) * w

    def test_forward_and_grads_match_xla(self):
        from paddle_tpu.kernels.rmsnorm import rmsnorm_residual_pallas

        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(16, 256), jnp.float32)
        r = jnp.asarray(rng.randn(16, 256), jnp.float32)
        w = jnp.asarray(rng.randn(256), jnp.float32)
        g = jnp.asarray(rng.randn(16, 256), jnp.float32)
        out, ssum = rmsnorm_residual_pallas(x, r, w)
        np.testing.assert_allclose(np.asarray(out), np.asarray(self._ref(x, r, w)),
                                   atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(np.asarray(ssum), np.asarray(x + r),
                                   atol=1e-6)
        gp = jax.grad(lambda *a: jnp.vdot(
            rmsnorm_residual_pallas(*a)[0], g), (0, 1, 2))(x, r, w)
        gr = jax.grad(lambda *a: jnp.vdot(self._ref(*a), g), (0, 1, 2))(x, r, w)
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-5, rtol=2e-5)

    def test_no_residual_variant(self):
        from paddle_tpu.kernels.rmsnorm import rmsnorm_pallas

        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(2, 8, 128), jnp.float32)
        w = jnp.asarray(rng.randn(128), jnp.float32)
        out = rmsnorm_pallas(x, w)
        ref = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * w
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        # grads flow (x appears as both core args; cotangents sum correctly)
        dx = jax.grad(lambda xx: jnp.sum(rmsnorm_pallas(xx, w) ** 2))(x)
        dr = jax.grad(lambda xx: jnp.sum(
            (xx * jax.lax.rsqrt(jnp.mean(xx * xx, -1, keepdims=True) + 1e-6)
             * w) ** 2))(x)
        np.testing.assert_allclose(np.asarray(dx), np.asarray(dr),
                                   atol=5e-5, rtol=5e-5)


class TestStreamingSoftmaxCE:
    def test_loss_and_grad_match_xla(self):
        from paddle_tpu.kernels.softmax_ce import softmax_ce_pallas

        rng = np.random.RandomState(2)
        x = jnp.asarray(rng.randn(32, 512) * 3, jnp.float32)
        lab = jnp.asarray(rng.randint(0, 512, 32), jnp.int32)

        def ref(xx):
            ls = jax.nn.log_softmax(xx, axis=-1)
            return -jnp.take_along_axis(ls, lab[:, None], axis=-1)[:, 0]

        lp = softmax_ce_pallas(x, lab)
        np.testing.assert_allclose(np.asarray(lp), np.asarray(ref(x)),
                                   atol=2e-5, rtol=2e-5)
        dp = jax.grad(lambda xx: jnp.sum(softmax_ce_pallas(xx, lab)))(x)
        dr = jax.grad(lambda xx: jnp.sum(ref(xx)))(x)
        np.testing.assert_allclose(np.asarray(dp), np.asarray(dr),
                                   atol=2e-5, rtol=2e-5)

    def test_batched_leading_dims(self):
        from paddle_tpu.kernels.softmax_ce import softmax_ce_pallas

        rng = np.random.RandomState(3)
        x = jnp.asarray(rng.randn(2, 8, 256), jnp.float32)
        lab = jnp.asarray(rng.randint(0, 256, (2, 8)), jnp.int64)
        loss = softmax_ce_pallas(x, lab)
        assert loss.shape == (2, 8)
        ref = -jnp.take_along_axis(jax.nn.log_softmax(x, -1),
                                   lab[..., None], -1)[..., 0]
        np.testing.assert_allclose(np.asarray(loss), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


class TestPolicyWiring:
    """The opt-in actually reaches the kernels (review finding: selectors
    with zero call sites would make FLAGS_use_pallas a no-op)."""

    def test_rms_norm_and_cross_entropy_optin_parity(self):
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F

        rng = np.random.RandomState(0)
        x = rng.randn(8, 256).astype(np.float32)
        w = rng.randn(256).astype(np.float32)
        lab = rng.randint(0, 256, (8,)).astype(np.int64)
        lab[::3] = -100  # ignore_index rows
        base_n = F.rms_norm(paddle.to_tensor(x), paddle.to_tensor(w)).numpy()
        base_ce = F.cross_entropy(paddle.to_tensor(x),
                                  paddle.to_tensor(lab)).numpy()
        kernels.set_use_pallas(True)
        try:
            opt_n = F.rms_norm(paddle.to_tensor(x),
                               paddle.to_tensor(w)).numpy()
            opt_ce = F.cross_entropy(paddle.to_tensor(x),
                                     paddle.to_tensor(lab)).numpy()
        finally:
            kernels.set_use_pallas(None)
        np.testing.assert_allclose(opt_n, base_n, atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(opt_ce, base_ce, atol=2e-5, rtol=2e-5)
