"""Flash-attention kernel parity (interpret mode on CPU).

The reference validates its vendored flash-attn against a naive softmax
attention (/root/reference/test/legacy_test/test_flash_attention.py); here the
Pallas kernel (HLO-interpret mode), the jnp mirror used inside sharded CPU
tests, and sdpa_ref must all agree on outputs and gradients.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.kernels.flash_attention import (
    _bwd_mirror, _flash_bhsd, _flash_fwd, _fwd_mirror, flash_attention_pallas,
)
from paddle_tpu.nn.functional.attention import sdpa_ref


def _rand_qkv(rng, B=2, S=64, Hq=4, Hk=4, D=16):
    q = rng.standard_normal((B, S, Hq, D)).astype(np.float32)
    k = rng.standard_normal((B, S, Hk, D)).astype(np.float32)
    v = rng.standard_normal((B, S, Hk, D)).astype(np.float32)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("gqa", [False, True])
def test_pallas_kernel_matches_sdpa_ref(causal, gqa):
    rng = np.random.default_rng(0)
    q, k, v = _rand_qkv(rng, Hk=2 if gqa else 4)

    def loss_pallas(q, k, v):
        return jnp.sum(flash_attention_pallas(q, k, v, is_causal=causal) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(sdpa_ref(q, k, v, is_causal=causal) ** 2)

    out_p = flash_attention_pallas(q, k, v, is_causal=causal)
    out_r = sdpa_ref(q, k, v, is_causal=causal)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                               atol=2e-5, rtol=2e-5)

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_jnp_mirror_matches_interpret_kernel(causal):
    """The mirror used inside sharded CPU tests must transcribe the kernel
    math exactly — fwd out + lse, and the bwd dq/dk/dv formulas."""
    rng = np.random.default_rng(1)
    B, S, D = 3, 32, 16
    q = jnp.asarray(rng.standard_normal((B, S, D)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((B, S, D)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((B, S, D)).astype(np.float32))
    sm = 1.0 / np.sqrt(D)

    out_k, lse_k = _flash_fwd(q, k, v, causal, sm)
    out_m, lse_m = _fwd_mirror(q, k, v, causal, sm)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_m),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(lse_k), np.asarray(lse_m),
                               atol=2e-5, rtol=2e-5)

    g = jnp.asarray(rng.standard_normal((B, S, D)).astype(np.float32))

    def f(q, k, v):
        return jnp.vdot(_flash_bhsd(q, k, v, causal, sm), g)

    dq_k, dk_k, dv_k = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    delta = jnp.sum(g * out_m.astype(jnp.float32), axis=-1, keepdims=True)
    dq_m, dk_m, dv_m = _bwd_mirror(q, k, v, g, lse_m, delta, causal, sm)
    for a, b in zip((dq_k, dk_k, dv_k), (dq_m, dk_m, dv_m)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# round 5: varlen (cu_seqlens), dense masks, dropout through the kernel
# (reference: flash_attn_unpadded at
#  /root/reference/python/paddle/nn/functional/flash_attention.py:272 and
#  the masked paths of scaled_dot_product_attention)
# ---------------------------------------------------------------------------

from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.kernels.flash_attention import flash_attn_varlen_pallas


class TestMaskedFlash:
    def test_bool_padding_mask_matches_oracle(self):
        rng = np.random.default_rng(2)
        B, S, H, D = 2, 256, 2, 32
        q, k, v = (jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
                   for _ in range(3))
        lens = jnp.array([200, 128])
        amask = (jnp.arange(S)[None, :] < lens[:, None])[:, None, None, :]
        out = flash_attention_pallas(q, k, v, attn_mask=amask)
        ref = sdpa_ref(q, k, v, attn_mask=amask)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5, rtol=3e-5)

    @pytest.mark.parametrize("mshape,mode", [
        ((1, 2, 256, 256), "head"), ((2, 1, 1, 256), "batch"),
        ((1, 1, 256, 256), "one"), ((2, 2, 256, 256), "bh")])
    def test_kernel_float_bias_modes(self, mshape, mode):
        """All four mask broadcast modes of the kernel (additive f32 bias,
        used internally — the public API routes float biases to einsum so
        the bias itself differentiates)."""
        rng = np.random.default_rng(3)
        B, S, H, D = 2, 256, 2, 32
        q, k, v = (jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
                   for _ in range(3))
        bias = jnp.asarray(rng.standard_normal(mshape), jnp.float32) * 0.5
        cm, cmode = fa._canon_mask(bias, B, H, S, S)
        assert cmode == mode
        smv = 1.0 / np.sqrt(D)

        def to_bhsd(x):
            return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)

        def lp(q, k, v):
            out, _ = fa._flash_core(to_bhsd(q), to_bhsd(k), to_bhsd(v),
                                    None, None, cm, None, True, smv, 0.0,
                                    H, cmode)
            return jnp.sum(out ** 2)

        def lr(q, k, v):
            return jnp.sum(sdpa_ref(q, k, v, attn_mask=bias, scale=smv,
                                    is_causal=True) ** 2)

        np.testing.assert_allclose(float(lp(q, k, v)), float(lr(q, k, v)),
                                   rtol=1e-4)
        gp = jax.grad(lp, (0, 1, 2))(q, k, v)
        gr = jax.grad(lr, (0, 1, 2))(q, k, v)
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4, rtol=5e-4)

    def test_public_float_bias_differentiates_through_mask(self):
        """A learnable additive bias passed to the public API must receive
        real gradients (routed to the einsum path; the kernel would treat
        the mask as a constant)."""
        rng = np.random.default_rng(30)
        B, S, H, D = 2, 64, 2, 16
        q, k, v = (jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
                   for _ in range(3))
        bias = jnp.asarray(rng.standard_normal((1, H, S, S)), jnp.float32)

        def lp(b):
            return jnp.sum(flash_attention_pallas(q, k, v, attn_mask=b) ** 2)

        def lr(b):
            return jnp.sum(sdpa_ref(q, k, v, attn_mask=b) ** 2)

        gp = jax.grad(lp)(bias)
        gr = jax.grad(lr)(bias)
        assert float(jnp.abs(gp).max()) > 0
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                                   atol=1e-5, rtol=1e-5)

    def test_mask_rejects_bad_shape(self):
        q = jnp.zeros((2, 64, 2, 16))
        with pytest.raises(ValueError, match="broadcastable"):
            flash_attention_pallas(
                q, q, q, attn_mask=jnp.zeros((3, 1, 1, 64), jnp.bool_))


class TestVarlenFlash:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_per_sequence_oracle(self, causal):
        rng = np.random.default_rng(4)
        H, D = 2, 32
        cu = jnp.array([0, 100, 228, 300], jnp.int32)
        T = 300
        q, k, v = (jnp.asarray(rng.standard_normal((T, H, D)), jnp.float32)
                   for _ in range(3))
        out = flash_attn_varlen_pallas(q, k, v, cu, cu, causal=causal)
        refs = [sdpa_ref(q[None, s:e], k[None, s:e], v[None, s:e],
                         is_causal=causal)[0]
                for s, e in zip([0, 100, 228], [100, 228, 300])]
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(jnp.concatenate(refs, 0)),
                                   atol=3e-5, rtol=3e-5)

    def test_grads_match_per_sequence_oracle(self):
        rng = np.random.default_rng(5)
        H, D = 2, 16
        cu = jnp.array([0, 60, 200, 256], jnp.int32)
        T = 256
        q, k, v = (jnp.asarray(rng.standard_normal((T, H, D)), jnp.float32)
                   for _ in range(3))

        def lp(q, k, v):
            return jnp.sum(flash_attn_varlen_pallas(
                q, k, v, cu, cu, causal=True) ** 2)

        def lr(q, k, v):
            tot = 0.0
            for s, e in zip([0, 60, 200], [60, 200, 256]):
                tot = tot + jnp.sum(sdpa_ref(q[None, s:e], k[None, s:e],
                                             v[None, s:e], is_causal=True) ** 2)
            return tot

        gp = jax.grad(lp, (0, 1, 2))(q, k, v)
        gr = jax.grad(lr, (0, 1, 2))(q, k, v)
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4, rtol=5e-4)

    def test_functional_unpadded_api(self):
        """nn.functional.flash_attn_unpadded: reference signature, (out, None)."""
        from paddle_tpu.nn.functional.attention import flash_attn_unpadded

        rng = np.random.default_rng(6)
        cu = jnp.array([0, 50, 128], jnp.int32)
        q, k, v = (jnp.asarray(rng.standard_normal((128, 2, 16)), jnp.float32)
                   for _ in range(3))
        out, sm = flash_attn_unpadded(q, k, v, cu, cu, 64, 64,
                                      scale=1.0 / 4.0, causal=True)
        assert sm is None
        assert tuple(out.shape) == (128, 2, 16)
        ref = jnp.concatenate([
            sdpa_ref(q[None, s:e], k[None, s:e], v[None, s:e],
                     is_causal=True, scale=0.25)[0]
            for s, e in [(0, 50), (50, 128)]], 0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5, rtol=3e-5)

    def test_block_skip_bounds(self):
        """The searchsorted block ranges must cover exactly the blocks a
        packed layout needs (skipping cross-sequence blocks)."""
        qseg = jnp.array([[0, 0, 0, 1, 1, 2, 2, 2]], jnp.int32)
        kseg = qseg
        lob, hib = fa._varlen_bounds_q(qseg, kseg, 2, 2, False)
        # q-blocks [0,0],[0,1],[1,2],[2,2]: seg0 spans k pos 0-2 (k-blocks
        # 0-1), seg1 pos 3-4, seg2 pos 5-7 -> block ranges below
        np.testing.assert_array_equal(np.asarray(lob)[0], [0, 0, 1, 2])
        np.testing.assert_array_equal(np.asarray(hib)[0], [2, 3, 4, 4])
        lob2, hib2 = fa._varlen_bounds_kv(qseg, kseg, 2, 2, False)
        np.testing.assert_array_equal(np.asarray(lob2)[0], [0, 0, 1, 2])
        np.testing.assert_array_equal(np.asarray(hib2)[0], [2, 3, 4, 4])


class TestDropoutFlash:
    def test_mirror_bwd_matches_autodiff_exactly(self):
        """With dropout, the custom_vjp backward formula must equal jax
        autodiff of the mirror forward (same seed -> same mask)."""
        rng = np.random.default_rng(7)
        BH, S, D = 4, 64, 16
        q, k, v = (jnp.asarray(rng.standard_normal((BH, S, D)), jnp.float32)
                   for _ in range(3))
        seed = jnp.array([7], jnp.int32)
        smv = 1.0 / np.sqrt(D)
        g = jnp.asarray(rng.standard_normal((BH, S, D)), jnp.float32)

        def mirror_out(q, k, v):
            out, _ = fa._mirror_fwd(q, k, v, None, None, None, seed, True,
                                    smv, 0.3, 1)
            return out

        def core_out(q, k, v):
            out, _ = fa._flash_core(q, k, v, None, None, None, seed, True,
                                    smv, 0.3, 1)
            return out

        truth = jax.grad(lambda *a: jnp.vdot(mirror_out(*a), g), (0, 1, 2))(q, k, v)
        mine = jax.grad(lambda *a: jnp.vdot(core_out(*a), g), (0, 1, 2))(q, k, v)
        for a, b in zip(mine, truth):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-5, rtol=2e-5)

    def test_dropout_statistics_and_determinism(self):
        rng = np.random.default_rng(8)
        B, S, H, D = 2, 128, 2, 16
        q, k = (jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
                for _ in range(2))
        v = jnp.ones((B, S, H, D), jnp.float32)
        o1 = flash_attention_pallas(q, k, v, dropout_p=0.4, fixed_seed=3)
        o2 = flash_attention_pallas(q, k, v, dropout_p=0.4, fixed_seed=3)
        o3 = flash_attention_pallas(q, k, v, dropout_p=0.4, fixed_seed=4)
        assert bool(jnp.allclose(o1, o2))
        assert not bool(jnp.allclose(o1, o3))
        # upscale-in-train keeps the mean ~1 with v = ones
        assert abs(float(o1.mean()) - 1.0) < 0.05
        # eval mode: no dropout
        oe = flash_attention_pallas(q, k, v, dropout_p=0.4, training=False)
        np.testing.assert_allclose(np.asarray(oe),
                                   np.asarray(flash_attention_pallas(q, k, v)),
                                   atol=1e-6)


class TestRingUsesFlashBlocks:
    def test_block_flash_merge_equals_full(self):
        """Splitting KV in two flash blocks and merging (out, lse) partials
        must equal one full flash call — the ring attention invariant."""
        from paddle_tpu.distributed.sequence_parallel import (
            _block_flash, _merge_partials)

        rng = np.random.default_rng(9)
        B, S, H, D = 2, 128, 2, 16
        q, k, v = (jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
                   for _ in range(3))
        smv = 1.0 / np.sqrt(D)
        o1, l1 = _block_flash(q, k[:, :64], v[:, :64], smv, False)
        o2, l2 = _block_flash(q, k[:, 64:], v[:, 64:], smv, False)
        merged, _ = _merge_partials(o1.astype(jnp.float32), l1,
                                    o2.astype(jnp.float32), l2)
        full, _ = _block_flash(q, k, v, smv, False)
        np.testing.assert_allclose(np.asarray(merged), np.asarray(full),
                                   atol=3e-5, rtol=3e-5)


class TestMosaicUnderAMesh:
    """GSPMD cannot partition a Mosaic custom call, so on a multi-device TPU
    mesh the compiled kernel must run under shard_map (found on the
    four-chip host; the CPU meshes never see it, interpret mode being plain
    HLO). Lowering for the TPU platform needs no TPU."""

    @pytest.fixture
    def compiled_kernel_on_mesh(self, monkeypatch):
        from paddle_tpu.distributed.mesh import (
            HybridCommunicateGroup, build_mesh, set_hybrid_communicate_group)

        monkeypatch.setattr(fa, "_interpret_mode", lambda: False)
        mesh = build_mesh(degrees={"dp": 2, "mp": 2})
        set_hybrid_communicate_group(HybridCommunicateGroup(None, mesh))
        yield mesh
        set_hybrid_communicate_group(None)

    @staticmethod
    def _lower(fn, mesh, B=4, S=256, H=4, D=128):
        from jax.sharding import NamedSharding, PartitionSpec as P

        x = jax.ShapeDtypeStruct((B, S, H, D), jnp.bfloat16,
                                 sharding=NamedSharding(mesh, P()))
        return jax.jit(fn).trace(x, x, x).lower(
            lowering_platforms=("tpu",)).as_text()

    def test_forward_and_backward_lower_under_shard_map(
            self, compiled_kernel_on_mesh):
        mesh = compiled_kernel_on_mesh
        spec = fa._mesh_shard_specs(4, 4, None)
        assert spec[1] == jax.sharding.PartitionSpec(
            ("dp",), None, ("mp",), None) and spec[2] == ("dp", "mp")

        def loss(q, k, v):
            return jnp.sum(flash_attention_pallas(
                q, k, v, is_causal=True).astype(jnp.float32))

        text = self._lower(jax.grad(loss, argnums=(0, 1, 2)), mesh)
        for name in ("flash_attention_fwd", "flash_attention_bwd_dq",
                     "flash_attention_bwd_dkv"):
            assert any(name in l and "tpu_custom_call" in l
                       for l in text.splitlines()), name

    def test_without_it_gspmd_refuses_the_kernel(
            self, compiled_kernel_on_mesh, monkeypatch):
        monkeypatch.setattr(fa, "_mesh_shard_specs", lambda *a: None)
        with pytest.raises(NotImplementedError, match="shard_map"):
            self._lower(lambda q, k, v: flash_attention_pallas(
                q, k, v, is_causal=True), compiled_kernel_on_mesh)

    def test_what_the_mapping_does_not_cover(self, compiled_kernel_on_mesh):
        # a batch that does not divide stays whole on every data rank
        assert fa._mesh_shard_specs(3, 4, None)[2] == ("mp",)
        with pytest.raises(NotImplementedError, match="'batch'-mode mask"):
            fa._mesh_shard_specs(4, 4, "batch")
        with pytest.raises(ValueError, match="3 attention heads"):
            fa._mesh_shard_specs(4, 3, None)
