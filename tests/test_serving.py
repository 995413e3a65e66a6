"""paddle_tpu.serving: paged KV cache, ragged paged attention, and the
continuous-batching engine.

The acceptance gate (mirrors ISSUE.md): concurrent requests of different
lengths through LLMEngine must produce token-for-token the same outputs as
independent uncached decoding, while the block pool stays inside its
high-water bound and the decode step compiles exactly once.
"""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu
from paddle_tpu.kernels.paged_attention import (
    paged_attention_pallas, paged_attention_ref, paged_decode_pallas,
    paged_decode_ref)
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.nn import sample_logits
from paddle_tpu.serving import (
    BlockAllocator, LLMEngine, PagedKVCache, SamplingParams, naive_generate)


def _tiny_model(vocab=61, hidden=32, layers=2, heads=4, kv_heads=2, seq=64):
    paddle_tpu.seed(0)
    cfg = llama_tiny(vocab=vocab, hidden=hidden, layers=layers, heads=heads,
                     kv_heads=kv_heads, inter=2 * hidden, seq=seq)
    return LlamaForCausalLM(cfg)


# ---------------------------------------------------------------------------
# block allocator
# ---------------------------------------------------------------------------

class TestBlockAllocator:
    def test_alloc_free_reuse_roundtrip(self):
        a = BlockAllocator(num_blocks=8)  # block 0 reserved -> 7 usable
        assert a.num_usable == 7 and a.num_free == 7
        first = a.alloc(3)
        assert sorted(first) == [1, 2, 3] and 0 not in first
        assert a.num_used == 3 and a.high_water == 3
        a.free(first[:2])
        assert a.num_used == 1 and a.num_free == 6
        again = a.alloc(6)  # must reuse the freed ids
        assert again is not None and set(first[:2]) <= set(again)
        assert a.high_water == 7 and a.num_free == 0

    def test_exhaustion_returns_none_not_partial(self):
        a = BlockAllocator(num_blocks=4)
        assert a.alloc(3) is not None
        before = a.num_used
        assert a.alloc(1) is None
        assert a.num_used == before  # nothing half-allocated

    def test_double_free_rejected(self):
        a = BlockAllocator(num_blocks=4)
        (b,) = a.alloc(1)
        a.free([b])
        with pytest.raises(ValueError):
            a.free([b])

    def test_cache_tables_and_utilization(self):
        c = PagedKVCache(num_layers=1, num_blocks=9, kv_heads=1,
                         block_size=4, head_dim=8)
        assert c.allocate("a", 10)          # 3 blocks
        assert c.extend("a", 13)            # 4th block
        assert c.utilization() == pytest.approx(4 / 8)
        tbl = c.table_array(["a", None], max_blocks=6)
        assert tbl.shape == (2, 6)
        assert list(tbl[0][:4]) == c.tables["a"] and all(tbl[1] == 0)
        c.free_seq("a")
        assert c.allocator.num_used == 0


# ---------------------------------------------------------------------------
# ragged paged attention kernel
# ---------------------------------------------------------------------------

def _published_decode_shapes(sharding=None):
    """The benchmark's decode call (Mistral-7B-v0.3 widths, the engine's 32
    slots of 128 blocks of 16, the default pool), as shapes."""
    S, Hq, Hkv, D, bs, M, N = 32, 32, 8, 128, 16, 128, 4096

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return (sds((S, Hq, D), jnp.bfloat16),
            sds((N, 2, Hkv, bs, D), jnp.bfloat16),
            sds((S, M), jnp.int32), sds((S,), jnp.int32))


@pytest.fixture(scope="module")
def v5e_chip():
    """One chip of a described (not attached) v5e host, for compiles that
    need no chip. Only here and only in a fixture: the process that
    describes a topology loads libtpu and keeps it until it exits."""
    import os

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else /tmp/tpu_logs
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


class TestPagedAttentionKernel:
    def _case(self, seed, S=4, Hq=4, Hkv=2, D=16, bs=8, N=12, M=3):
        rng = np.random.RandomState(seed)
        q = jnp.asarray(rng.randn(S, Hq, D).astype(np.float32))
        pool = jnp.asarray(rng.randn(N, 2, Hkv, bs, D).astype(np.float32))
        bt = jnp.asarray(rng.randint(0, N, (S, M)).astype(np.int32))
        ctx = jnp.asarray(rng.randint(1, M * bs + 1, (S,)).astype(np.int32))
        return q, pool, bt, ctx

    def test_mirror_matches_bruteforce(self):
        q, pool, bt, ctx = self._case(0)
        out = np.asarray(paged_attention_ref(q, pool, bt, ctx))
        S, Hq, D = q.shape
        Hkv, bs = pool.shape[2], pool.shape[3]
        rep = Hq // Hkv
        for s in range(S):
            k = np.concatenate(
                [np.asarray(pool[bt[s, j], 0]) for j in range(bt.shape[1])],
                axis=1)
            v = np.concatenate(
                [np.asarray(pool[bt[s, j], 1]) for j in range(bt.shape[1])],
                axis=1)
            c = int(ctx[s])
            for h in range(Hq):
                kh, vh = k[h // rep][:c], v[h // rep][:c]
                lo = (np.asarray(q)[s, h] @ kh.T) / math.sqrt(D)
                p = np.exp(lo - lo.max())
                p /= p.sum()
                np.testing.assert_allclose(p @ vh, out[s, h], atol=1e-5)

    def test_pallas_interpret_matches_mirror(self):
        for seed in (0, 1):
            q, pool, bt, ctx = self._case(seed)
            ref = paged_attention_ref(q, pool, bt, ctx)
            pal = paged_attention_pallas(q, pool, bt, ctx, interpret=True)
            np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                                       atol=1e-5)

    # the walk's shapes on the CPU: 40 blocks of 8 a slot, so a compute step
    # is _pages_per_step = 16 pages = 128 tokens and a full slot takes
    # three steps, the last one half empty
    WALK = dict(S=4, Hkv=2, D=16, bs=8, M=40)
    _STEP = 16 * 8
    _FULL = 40 * 8
    WALK_CONTEXTS = {
        "one_token": [1, 1, 1, 1],
        "block_boundary": [8 - 1, 8, 8 + 1, 2 * 8 + 1],
        "step_boundary": [_STEP - 1, _STEP, _STEP + 1, 2 * _STEP],
        "inactive_beside_full": [1, _FULL, 1, _FULL],
        "ragged": [3, _STEP + 8 + 5, 2 * _STEP - 1, _FULL - 1],
    }
    _walk_fns = {}

    @pytest.mark.parametrize("dtype,rep,atol", [
        ("float32", 1, 1e-5), ("float32", 4, 1e-5),
        # bf16 pool and query: the kernel rounds q * scale and the
        # probabilities to bf16 before its dots (2^-9 relative each, as the
        # MXU's DEFAULT precision did to the old kernel's f32 tiles) and
        # both sides round the output to bf16 (2^-9 of |out| <= 3.2 is
        # 0.006): 0.002 observed, 0.02 allowed, against 1e4 for a page
        # folded in by mistake
        ("bfloat16", 4, 2e-2)])
    @pytest.mark.parametrize("contexts", list(WALK_CONTEXTS))
    def test_walk_matches_mirror(self, contexts, dtype, rep, atol):
        """The Pallas body in interpret mode (the installed interpreter runs
        the async copies, semaphores and SMEM scratch as they are, and hands
        out uninitialised VMEM as NaN) against the mirror, over what a walk
        of live pages can get wrong. Every pool position that holds no
        context (unreferenced blocks, block 0, which dead table entries
        point at, and the tail of each slot's last block) is 1e4: large,
        finite, and visible in the output if it is ever folded in."""
        from paddle_tpu.kernels.paged_attention import _pages_per_step

        w = self.WALK
        S, Hkv, D, bs, M = w["S"], w["Hkv"], w["D"], w["bs"], w["M"]
        assert _pages_per_step(bs, Hkv, D, 4, M) * bs == self._STEP
        ctx = np.asarray(self.WALK_CONTEXTS[contexts], np.int32)
        rng = np.random.RandomState(len(contexts) + rep)
        N = S * M + 1
        pool = np.full((N, 2, Hkv, bs, D), 1e4, np.float32)
        bt = np.zeros((S, M), np.int32)
        blocks = iter(1 + rng.permutation(N - 1))
        for s in range(S):
            for j in range(-(-ctx[s] // bs)):
                bt[s, j] = b = next(blocks)
                n = min(bs, ctx[s] - j * bs)
                pool[b, :, :, :n] = rng.randn(2, Hkv, n, D)
        q = rng.randn(S, Hkv * rep, D)
        args = (jnp.asarray(q, dtype), jnp.asarray(pool, dtype),
                jnp.asarray(bt), jnp.asarray(ctx))
        # one trace a (dtype, rep): the contexts are data, not shapes
        fn = self._walk_fns.setdefault((dtype, rep), jax.jit(
            lambda *a: paged_attention_pallas(*a, interpret=True)))
        got = np.asarray(fn(*args)).astype(np.float32)
        ref = np.asarray(paged_attention_ref(*args)).astype(np.float32)
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, ref, atol=atol)

    @staticmethod
    def _mosaic_calls(lowered_text):
        return [l for l in lowered_text.splitlines()
                if "paged_attention" in l and "tpu_custom_call" in l]

    @pytest.mark.parametrize("Hq,Hkv", [(8, 8), (8, 2)])   # MHA: rep = 1
    def test_lowers_for_mosaic(self, Hq, Hkv):
        """What the interpreter never checks: Mosaic's block-shape rule (the
        query block must tile for any rep) and its dot precisions (the
        package default, tensorfloat32, is not one). Lowering for the TPU
        platform needs no TPU."""
        S, D, bs, N, M = 4, 128, 16, 9, 2

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype)

        text = jax.jit(
            lambda *a: paged_attention_pallas(*a, interpret=False)).trace(
            sds((S, Hq, D), jnp.bfloat16),
            sds((N, 2, Hkv, bs, D), jnp.bfloat16),
            sds((S, M), jnp.int32), sds((S,), jnp.int32)).lower(
            lowering_platforms=("tpu",)).as_text()
        assert self._mosaic_calls(text)

    def test_lowers_at_published_shape(self):
        """The benchmark's own decode call: one Mosaic call, named for the
        roofline metric's reader, and nothing of the attention beside it."""
        text = jax.jit(
            lambda *a: paged_attention_pallas(*a, interpret=False)).trace(
            *_published_decode_shapes()).lower(
            lowering_platforms=("tpu",)).as_text()
        assert len(self._mosaic_calls(text)) == 1
        assert text.count("tpu_custom_call") == 1
        assert "stablehlo.dot" not in text and "stablehlo.exp" not in text

    def test_compiles_at_published_shape_for_v5e(self, v5e_chip):
        """The TPU compiler, for a described v5e chip, refuses a kernel that
        overruns VMEM or slices against the tiling ("RESOURCE_EXHAUSTED: Ran
        out of memory in memory space vmem", PERF.md 7 a), as the chip
        would; the same with an f32 pool, whose pages are twice the bytes."""
        fn = jax.jit(lambda *a: paged_attention_pallas(*a, interpret=False))
        shapes = _published_decode_shapes(v5e_chip)
        fn.trace(*shapes).lower(lowering_platforms=("tpu",)).compile()
        f32 = [jax.ShapeDtypeStruct(a.shape, jnp.float32, sharding=v5e_chip)
               if a.dtype == jnp.bfloat16 else a for a in shapes]
        fn.trace(*f32).lower(lowering_platforms=("tpu",)).compile()

    # a window over the same walk: shorter than a step, a step and a half,
    # longer than any context
    @pytest.mark.parametrize("window", [8 - 3, _STEP + 8 * 8 + 3, 2 * _FULL])
    @pytest.mark.parametrize("contexts", list(WALK_CONTEXTS))
    @pytest.mark.parametrize("rep", [6, 8])
    def test_window_walk_matches_mirror(self, contexts, window, rep):
        """The walk that starts at the page holding position ctx - window:
        the pages before it hold 1e4 here too (they are some request's K/V
        on the chip, but not this query's to see), so one folded in shows."""
        w = self.WALK
        S, Hkv, D, bs, M = w["S"], w["Hkv"], w["D"], w["bs"], w["M"]
        ctx = np.asarray(self.WALK_CONTEXTS[contexts], np.int32)
        rng = np.random.RandomState(len(contexts) + window)
        N = S * M + 1
        pool = np.full((N, 2, Hkv, bs, D), 1e4, np.float32)
        bt = np.zeros((S, M), np.int32)
        blocks = iter(1 + rng.permutation(N - 1))
        for s in range(S):
            first = max(ctx[s] - window, 0)
            for j in range(-(-ctx[s] // bs)):
                bt[s, j] = b = next(blocks)
                lo = min(max(first - j * bs, 0), bs)
                hi = min(bs, ctx[s] - j * bs)
                pool[b, :, :, lo:hi] = rng.randn(2, Hkv, hi - lo, D)
        q = rng.randn(S, Hkv * rep, D)
        args = (jnp.asarray(q, jnp.float32), jnp.asarray(pool),
                jnp.asarray(bt), jnp.asarray(ctx))
        fn = self._walk_fns.setdefault(("window", window, rep), jax.jit(
            lambda *a: paged_attention_pallas(*a, window=window,
                                              interpret=True)))
        got = np.asarray(fn(*args))
        ref = np.asarray(paged_attention_ref(*args, window=window))
        assert np.isfinite(got).all() and np.abs(ref).max() < 10
        np.testing.assert_allclose(got, ref, atol=1e-5)
        if window >= self._FULL:     # a window no context fills masks nothing
            np.testing.assert_allclose(
                ref, np.asarray(paged_attention_ref(*args)), atol=1e-6)

    def test_window_mirror_matches_bruteforce(self):
        q, pool, bt, ctx = self._case(3)
        window = 5
        out = np.asarray(paged_attention_ref(q, pool, bt, ctx, window=window))
        rep = q.shape[1] // pool.shape[2]
        for s in range(q.shape[0]):
            k = np.concatenate([np.asarray(pool[bt[s, j], 0])
                                for j in range(bt.shape[1])], axis=1)
            v = np.concatenate([np.asarray(pool[bt[s, j], 1])
                                for j in range(bt.shape[1])], axis=1)
            c = int(ctx[s])
            for h in range(q.shape[1]):
                kh = k[h // rep][max(c - window, 0):c]
                vh = v[h // rep][max(c - window, 0):c]
                lo = (np.asarray(q)[s, h] @ kh.T) / math.sqrt(q.shape[2])
                p = np.exp(lo - lo.max())
                np.testing.assert_allclose(p / p.sum() @ vh, out[s, h],
                                           atol=1e-5)

    def test_without_a_window_the_traced_kernel_is_the_one_before_windows(
            self):
        """``window=None`` traces nothing of the window's: no op reads
        ``ctx - window``, and the jaxpr is that of a call that never heard
        of the argument."""
        plain = jax.make_jaxpr(lambda *a: paged_attention_pallas(
            *a, interpret=False))(*_published_decode_shapes())
        none = jax.make_jaxpr(lambda *a: paged_attention_pallas(
            *a, window=None, interpret=False))(*_published_decode_shapes())
        windowed = jax.make_jaxpr(lambda *a: paged_attention_pallas(
            *a, window=512, interpret=False))(*_published_decode_shapes())
        assert str(plain) == str(none)
        assert len(str(windowed)) > len(str(plain))

    @pytest.mark.parametrize("Hq,window", [(48, None), (64, 512)])
    def test_compiles_with_groups_of_6_and_8_for_v5e(self, v5e_chip, Hq,
                                                     window):
        """Laguna-XS.2's decode calls: 6 query heads a KV head in the full
        layers, 8 and a window of 512 in the sliding ones."""
        fn = jax.jit(lambda *a: paged_attention_pallas(
            *a, window=window, interpret=False))
        q, pool, bt, ctx = _published_decode_shapes(v5e_chip)
        q = jax.ShapeDtypeStruct((q.shape[0], Hq, q.shape[2]), q.dtype,
                                 sharding=v5e_chip)
        fn.trace(q, pool, bt, ctx).lower(
            lowering_platforms=("tpu",)).compile()

    # the in-place write over the same walk; a context counts the new
    # token, whose row is position ctx - 1: row 0 of a page it opens (in a
    # later compute step too), the last row, a middle one
    WRITE_CONTEXTS = {
        "one_token": [1, 1, 1, 1],
        "row_first_last_mid": [2 * 8 + 1, 3 * 8, 8 + 4, _STEP + 1],
        "inactive_beside_live": [2, _FULL, 2, 5],
        "ragged": [3, _STEP + 8 + 5, 2 * _STEP - 1, _FULL],
    }

    @pytest.mark.parametrize("dtype,rep,window,atol", [
        ("float32", 1, None, 1e-5), ("float32", 4, None, 1e-5),
        ("bfloat16", 4, None, 2e-2),
        ("float32", 6, _STEP + 8 * 8 + 3, 1e-5), ("bfloat16", 8, 8 - 3, 2e-2)])
    @pytest.mark.parametrize("contexts", list(WRITE_CONTEXTS))
    def test_write_walk_matches_mirror(self, contexts, dtype, rep, window,
                                       atol):
        """The decode call on the whole pool (interpret mode against the
        mirror): the new row is attended over and is the only thing written.
        The middle layer of three is the call's; where it holds no context
        it is 1e4, the new row's own place too, so a row put in after the
        dots, or not at all, shows in the output. A slot with the all-zero
        table is inactive, as the engine has it (a context of 2, the
        reserved block 0): it may write nothing but its row of block 0."""
        w = self.WALK
        S, Hkv, D, bs, M = w["S"], w["Hkv"], w["D"], w["bs"], w["M"]
        L, layer = 3, 1
        ctx = np.asarray(self.WRITE_CONTEXTS[contexts], np.int32)
        inactive = (contexts == "inactive_beside_live") & (ctx == 2)
        rng = np.random.RandomState(len(contexts) + rep)
        N = S * M + 1
        pool = rng.randn(L, N, 2, Hkv, bs, D).astype(np.float32)
        pool[layer] = 1e4
        bt = np.zeros((S, M), np.int32)
        blocks = iter(1 + rng.permutation(N - 1))
        for s in np.flatnonzero(~inactive):
            first = 0 if window is None else max(ctx[s] - window, 0)
            for j in range(-(-ctx[s] // bs)):
                bt[s, j] = b = next(blocks)
                lo = min(max(first - j * bs, 0), bs)
                hi = min(bs, ctx[s] - 1 - j * bs)    # the new row stays 1e4
                if hi > lo:
                    pool[layer, b, :, :, lo:hi] = rng.randn(
                        2, Hkv, hi - lo, D)
        q = rng.randn(S, Hkv * rep, D)
        k_new, v_new = rng.randn(2, S, Hkv, D)
        args = (*(jnp.asarray(a, dtype) for a in (q, k_new, v_new, pool)),
                jnp.asarray(bt), jnp.asarray(ctx))
        fn = self._walk_fns.setdefault(("write", dtype, rep, window), jax.jit(
            lambda *a: paged_decode_pallas(*a, layer_idx=layer,
                                           window=window, interpret=True)))
        got, got_pool = fn(*args)
        ref, ref_pool = paged_decode_ref(*args, layer_idx=layer,
                                         window=window)
        got = np.asarray(got).astype(np.float32)[~inactive]
        ref = np.asarray(ref).astype(np.float32)[~inactive]
        assert np.isfinite(got).all() and np.abs(ref).max() < 10
        np.testing.assert_allclose(got, ref, atol=atol)
        # the pool: the mirror's, bit for bit (block 0 apart, where the
        # inactive slots' rows race in both) ...
        before = np.asarray(args[3]).astype(np.float32)
        got_pool = np.asarray(got_pool).astype(np.float32)
        ref_pool = np.asarray(ref_pool).astype(np.float32)
        assert (got_pool[:, 1:] == ref_pool[:, 1:]).all()
        # ... and the operand's but for one row a live slot, which holds
        # the slot's new K/V
        changed = got_pool != before
        assert not changed[[0, 2]].any()
        if inactive.any():
            changed[layer, 0, :, :, 1] = False
        assert not changed[layer, 0].any()
        new = np.asarray(jnp.stack(args[1:3], 1)).astype(np.float32)
        for s in np.flatnonzero(~inactive):
            b, off = bt[s, (ctx[s] - 1) // bs], (ctx[s] - 1) % bs
            assert (got_pool[layer, b, :, :, off] == new[s]).all()
            changed[layer, b, :, :, off] = False
        assert not changed[layer, 1:].any()

    def test_single_token_context(self):
        q, pool, bt, _ = self._case(2)
        ctx = jnp.ones(q.shape[0], jnp.int32)
        out = np.asarray(paged_attention_ref(q, pool, bt, ctx))
        # softmax over one position == that position's V
        first = np.asarray(pool[bt[:, 0], 1, :, 0])        # [S, Hkv, D]
        rep = q.shape[1] // pool.shape[2]
        np.testing.assert_allclose(out, np.repeat(first, rep, axis=1),
                                   atol=1e-6)


# ---------------------------------------------------------------------------
# the decode step's cache path, compiled for the chip: the pool stays put
# ---------------------------------------------------------------------------

class TestDecodeKeepsThePoolInPlace:
    # the serve cells' decode steps: layers' query heads and windows, pool
    CELLS = {
        "mistral": ([32] * 8, None, jnp.bfloat16),
        "mistral_f32_pool": ([32] * 8, None, jnp.float32),
        "laguna": ([48, 64, 64, 64, 48], [None, 512, 512, 512, None],
                   jnp.bfloat16),
    }

    @pytest.fixture
    def on_the_chip(self, monkeypatch):
        """What a process on a TPU would pick: the Pallas path, compiled by
        Mosaic (this process's backend is the CPU)."""
        from paddle_tpu import kernels
        from paddle_tpu.kernels import paged_attention

        monkeypatch.setattr(paged_attention, "_interpret_mode", lambda: False)
        kernels.set_use_pallas(True)
        yield
        kernels.set_use_pallas(None)

    @pytest.mark.parametrize("cell", list(CELLS))
    def test_no_pool_shaped_copy_in_the_compiled_step(self, v5e_chip,
                                                      on_the_chip, cell):
        """``PagedCacheView.attend`` once a layer, one after the other as a
        model calls it, the pool donated as the engine donates it, at the
        cell's shapes (4,097 blocks of 16, 32 slots, 8 KV heads of 128): in
        the optimised HLO the pool is the entry parameter, one
        ``paged_attention`` custom call a layer that takes it whole and
        hands it on, and the output, which is the parameter's buffer.
        Nothing else has the pool's shape or a layer's: no ``copy``,
        ``scatter``, ``dynamic-update-slice`` or fusion. (Before the kernel
        wrote the row itself this failed, in the Mistral cell with sixteen
        ``scatter``, two ``copy`` of the whole pool, between XLA's layout
        for the scatters and the custom call's, and eight fusions that each
        materialised one layer's ``[N, 2, H, bs, D]`` as the call's
        operand: 25 of the 33.5 ms of a step on the chip, PERF.md Findings
        PR 30.)"""
        import re
        from collections import Counter

        from paddle_tpu.serving.kv_cache import PagedCacheView

        heads, windows, pool_dtype = self.CELLS[cell]
        L, N, S, Hkv, D, bs, M = len(heads), 4097, 32, 8, 128, 16, 128

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

        def step(pool, bt, ctx, qs, k, v):
            view = PagedCacheView(pool, bt, ctx, bs, windows=windows)
            x = jnp.zeros((), k.dtype)
            for layer, q in enumerate(qs):
                x = view.attend(layer, q + x, k + x, v + x).sum().astype(
                    k.dtype)
            return view.pool, x

        compiled = jax.jit(step, donate_argnums=(0,)).trace(
            sds((L, N, 2, Hkv, bs, D), pool_dtype), sds((S, M), jnp.int32),
            sds((S,), jnp.int32),
            [sds((S, 1, h, D), jnp.bfloat16) for h in heads],
            sds((S, 1, Hkv, D), jnp.bfloat16),
            sds((S, 1, Hkv, D), jnp.bfloat16)).lower(
            lowering_platforms=("tpu",)).compile()
        text = compiled.as_text()
        layer_shape = f"[{N},2,{Hkv},{bs},{D}]"
        pool_shape = f"[{L},{layer_shape[1:]}"
        # %name = <result type> opcode(operands), ...
        instr = re.compile(r"^\s*(?:ROOT )?%\S+ = (.*?) ([a-z][a-z-]*)\(")
        ops = [m.group(2) for m in map(instr.match, text.splitlines())
               if m and (pool_shape in m.group(1)
                         or layer_shape in m.group(1))]
        calls = [l for l in text.splitlines()
                 if "custom-call(" in l and "paged_attention" in l]
        assert sorted(set(ops)) == ["custom-call", "get-tuple-element",
                                    "parameter", "tuple"], Counter(ops)
        assert len(calls) == L and all(pool_shape in c for c in calls)
        assert ops.count("custom-call") == L and ops.count("parameter") == 1
        assert re.search(r"input_output_alias=\{ \{0\}: \(0, \{\}", text)
        layer_bytes = N * 2 * Hkv * bs * D * jnp.dtype(pool_dtype).itemsize
        assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes


class TestPrefillAttentionIsOneKernelCall:
    """A prompt's attention, compiled for the chip: from
    ``PREFILL_KERNEL_MIN_LEN`` tokens on, one ``flash_attention_fwd`` Mosaic
    call a layer and no ``[H, P, P]`` score buffer anywhere (the einsum
    composition wrote one in bf16 and read it twice, 0.56 GB a 64-head
    layer at P = 1024: ``jit_prefill/fusion``, PERF.md §5); below it, that
    composition."""

    # the serve cells' prefills: layers' query heads, windows, KV heads
    CELLS = {
        "mistral": ([32] * 8, None, 8),
        "laguna": ([48, 64, 64, 64, 48], [None, 512, 512, 512, None], 8),
        "falcon": ([20] * 6, None, 4),
    }

    @pytest.fixture
    def on_the_chip(self, monkeypatch):
        from paddle_tpu import kernels
        from paddle_tpu.kernels import flash_attention

        monkeypatch.setattr(flash_attention, "_interpret_mode", lambda: False)
        kernels.set_use_pallas(True)
        yield
        kernels.set_use_pallas(None)

    # 512 is under kernels.PREFILL_KERNEL_MIN_LEN: the einsum composition
    @pytest.mark.parametrize("P", [512, 1024, 2048])
    @pytest.mark.parametrize("cell", list(CELLS))
    def test_one_kernel_call_a_layer_and_no_square(self, v5e_chip,
                                                    on_the_chip, cell, P):
        import re

        from paddle_tpu.kernels import PREFILL_KERNEL_MIN_LEN
        from paddle_tpu.serving.kv_cache import PagedCacheView

        kernel = P >= PREFILL_KERNEL_MIN_LEN

        heads, windows, Hkv = self.CELLS[cell]
        L, D, bs = len(heads), 128, 16
        N = P // bs + 1

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

        def prefill(pool, bt, valid_len, qs, k, v):
            view = PagedCacheView(pool, bt, None, bs, windows=windows,
                                  valid_len=valid_len)
            x = jnp.zeros((), k.dtype)
            for layer, q in enumerate(qs):
                x = view.attend(layer, q + x, k + x, v + x).sum().astype(
                    k.dtype)
            assert view.attention_paths == {"kernel": L * kernel,
                                            "materialised": L * (not kernel)}
            return view.pool, x

        text = jax.jit(prefill, donate_argnums=(0,)).trace(
            sds((L, N, 2, Hkv, bs, D), jnp.bfloat16),
            sds((1, P // bs), jnp.int32), sds((), jnp.int32),
            [sds((1, P, h, D), jnp.bfloat16) for h in heads],
            sds((1, P, Hkv, D), jnp.bfloat16),
            sds((1, P, Hkv, D), jnp.bfloat16)).lower(
            lowering_platforms=("tpu",)).compile().as_text()
        calls = [l for l in text.splitlines()
                 if "custom-call(" in l and "flash_attention_fwd" in l]
        assert len(calls) == L * kernel
        assert bool(re.search(rf"\[(\d+,)*{P},{P}\]", text)) != kernel


class TestStateCacheStaysInPlace:
    """The per-slot state arrays of a model with state layers, beside the
    pool and held to the same structure: at Falcon-H1-34B's widths (6
    layers, 64 slots, 32 heads of 128 by 256 float32: 1.61 GB) one copy a
    layer would cost what PR 30 removed."""

    @pytest.fixture
    def on_the_chip(self, monkeypatch):
        from paddle_tpu import kernels
        from paddle_tpu.kernels import (paged_attention, ssd_chunk_scan,
                                        ssm_state_update)

        for mod in (paged_attention, ssd_chunk_scan, ssm_state_update):
            monkeypatch.setattr(mod, "_interpret_mode", lambda: False)
        kernels.set_use_pallas(True)
        yield
        kernels.set_use_pallas(None)

    def test_no_state_shaped_copy_in_the_compiled_decode_step(
            self, v5e_chip, on_the_chip):
        """``PagedCacheView.shift`` and ``.recur`` once a layer, as a model
        calls them, the state donated as the engine donates it: in the
        optimised HLO the recurrent state is the entry parameter, one
        ``ssm_state_update`` custom call a layer that takes it whole and
        hands it on, and the output, which is the parameter's buffer.
        Nothing else has its shape or a layer's: no ``copy``, ``scatter``,
        ``dynamic-update-slice`` or fusion."""
        import re
        from collections import Counter

        from paddle_tpu.serving.kv_cache import PagedCacheView

        L, S, H, N, P, G, K1, C = 6, 64, 32, 256, 128, 2, 3, 5120

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

        def step(state, conv, bt, ctx, u, x, dt, a, b, c):
            view = PagedCacheView(None, bt, ctx, 16, state=(state, conv))
            acc = jnp.zeros((), x.dtype)
            for layer in range(L):
                window = view.shift(layer, u + acc)
                y = view.recur(layer, x + acc, dt, a, b, c, chunk=128)
                acc = (y.sum() + window.sum()).astype(x.dtype)
            return view.state, acc

        bf = jnp.bfloat16
        compiled = jax.jit(step, donate_argnums=(0, 1)).trace(
            sds((L, S, H, N, P), jnp.float32), sds((L, S, K1, C), bf),
            sds((S, 96), jnp.int32), sds((S,), jnp.int32),
            sds((S, 1, C), bf), sds((S, 1, H, P), bf),
            sds((S, 1, H), jnp.float32), sds((H,), jnp.float32),
            sds((S, 1, G, N), bf), sds((S, 1, G, N), bf)).lower(
            lowering_platforms=("tpu",)).compile()
        text = compiled.as_text()
        layer_shape = f"[{S},{H},{N},{P}]"
        state_shape = f"[{L},{layer_shape[1:]}"
        instr = re.compile(r"^\s*(?:ROOT )?%\S+ = (.*?) ([a-z][a-z-]*)\(")
        ops = [m.group(2) for m in map(instr.match, text.splitlines())
               if m and (state_shape in m.group(1)
                         or layer_shape in m.group(1))]
        calls = [l for l in text.splitlines()
                 if "custom-call(" in l and "ssm_state_update" in l]
        assert sorted(set(ops)) == ["custom-call", "get-tuple-element",
                                    "parameter", "tuple"], Counter(ops)
        assert len(calls) == L and all(state_shape in c for c in calls)
        assert ops.count("custom-call") == L and ops.count("parameter") == 1
        assert re.search(r"input_output_alias=\{ \{0\}: \(0, \{\}", text)
        layer_bytes = S * H * N * P * 4
        assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes

    def test_the_compiled_prefill_has_no_prompt_by_vocabulary_array(
            self, v5e_chip, on_the_chip):
        """The engine's own ``jit(prefill)`` of a Falcon-H1 (mixer heads of
        128, vocabulary 8,192, a prompt bucket of 256) compiled for the
        chip: the head is applied to the sampled position alone, so nothing
        in the optimised HLO is ``[256, 8192]``; both kernels are there, and
        the state is written by in-place slices. A Llama engine's prefill
        at the same sizes has the array (it keeps every position's
        logits), so the search can find one."""
        import re

        from paddle_tpu.models import (FalconH1Config, FalconH1ForCausalLM,
                                       LlamaConfig, LlamaForCausalLM)

        P, V = 256, 8192
        falcon = FalconH1ForCausalLM(FalconH1Config(
            vocab_size=V, hidden_size=384, intermediate_size=512,
            num_hidden_layers=2, num_attention_heads=5,
            num_key_value_heads=1, head_dim=128, max_position_embeddings=512,
            mamba_d_ssm=1024, mamba_n_heads=8, mamba_d_head=128,
            mamba_d_state=128, mamba_n_groups=1))
        llama = LlamaForCausalLM(LlamaConfig(
            vocab_size=V, hidden_size=384, intermediate_size=512,
            num_hidden_layers=2, num_attention_heads=3,
            num_key_value_heads=1, max_position_embeddings=512))
        wide = re.compile(rf"\[(?:1,)?{P},{V}\]")

        def compiled_prefill(model):
            model.to(dtype="bfloat16")
            eng = LLMEngine(model, block_size=16, max_slots=2,
                            max_model_len=512)
            eng._suspend_trace_counts = True

            def sds(a):
                return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                            sharding=v5e_chip)

            i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=v5e_chip)
            f32 = jax.ShapeDtypeStruct((), jnp.float32, sharding=v5e_chip)
            args = [jax.tree.map(sds, eng.params),
                    jax.tree.map(sds, eng.buffers), sds(eng.cache.pool),
                    jax.ShapeDtypeStruct((P,), jnp.int32, sharding=v5e_chip),
                    i32, jax.ShapeDtypeStruct((P // 16,), jnp.int32,
                                              sharding=v5e_chip),
                    f32, i32, f32, i32, i32]
            if eng.cache.state is not None:
                args += [i32, *map(sds, eng.cache.state)]
            text = eng._get_prefill_fn(P).trace(*args).lower(
                lowering_platforms=("tpu",)).compile().as_text()
            eng.close()
            return text

        text = compiled_prefill(falcon)
        assert not wide.search(text)
        for kernel in ("ssd_chunk_scan", "paged_attention"):
            assert (kernel == "ssd_chunk_scan") == any(
                "custom-call(" in l and kernel in l
                for l in text.splitlines()), kernel
        assert wide.search(compiled_prefill(llama))


# ---------------------------------------------------------------------------
# the decode step sorts only when a row samples
# ---------------------------------------------------------------------------

class TestDecodeSortsOnlyWhenARowSamples:
    def test_every_sort_of_the_compiled_step_is_in_a_conditional(self):
        """The optimised HLO of the engine's own ``jit(decode)`` on the tiny
        model keeps a real ``conditional`` (a ``vmap`` over the sampler, or
        a compiler that turned it into a ``select``, would run both
        branches), and each ``sort`` is in a computation reached from its
        branches alone: a greedy batch executes none."""
        import re

        eng = LLMEngine(_tiny_model(), block_size=8, max_slots=2,
                        max_model_len=64)
        eng.add_request([1, 2, 3], SamplingParams(max_new_tokens=8))
        eng.step()                      # a prefill and one decode step
        _, host = eng._assemble_decode()
        eng._suspend_trace_counts = True
        text = eng._get_decode_fn().lower(
            eng.params, eng.buffers, eng.cache.pool, eng._tokens,
            *map(jnp.asarray, host)).compile().as_text()
        eng.close()
        # computation name -> its lines
        comps, name = {}, None
        for line in text.splitlines():
            m = re.match(r"^(?:ENTRY )?%(\S+) \(.*\{$", line)
            if m:
                name = m.group(1)
                comps[name] = []
            elif name is not None:
                comps[name].append(line)

        def called(lines):
            for line in lines:
                for m in re.finditer(
                        r"(?:calls|to_apply|body|condition|true_computation"
                        r"|false_computation)=%([\w.\-]+)", line):
                    yield m.group(1)
                for m in re.finditer(r"branch_computations=\{([^}]*)\}",
                                     line):
                    yield from re.findall(r"%([\w.\-]+)", m.group(1))

        conditionals = [line for lines in comps.values() for line in lines
                        if " conditional(" in line]
        assert len(conditionals) == 1, conditionals
        under, todo = set(), list(called(conditionals))
        while todo:
            c = todo.pop()
            if c not in under:
                under.add(c)
                todo.extend(called(comps[c]))
        sorts = [c for c, lines in comps.items()
                 for line in lines if " sort(" in line]
        assert len(sorts) == 2 and set(sorts) <= under, sorts

    def test_a_sampling_request_is_counted_and_moves_no_greedy_token(self):
        """``sampled_step_share``: 0.0 while every request is greedy; above
        0 once a ``temperature=0.8`` request runs among greedy ones, whose
        tokens are those they get alone (the taken branch keeps the greedy
        rows' argmax)."""
        rng = np.random.RandomState(4)
        prompts = [list(rng.randint(0, 61, n)) for n in (5, 9, 3)]
        greedy = SamplingParams(max_new_tokens=6)
        eng = LLMEngine(_tiny_model(), block_size=8, max_slots=4,
                        max_model_len=64)
        eng._decode_tl.clear()          # the process's timeline: start clean
        alone = eng.generate(prompts, greedy)
        step = eng.stats()["perf"]["decode_step"]
        assert step["steps"] == 5 and step["sampled_step_share"] == 0.0
        reqs = [eng.add_request(p, greedy) for p in prompts]
        hot = eng.add_request(list(rng.randint(0, 61, 7)), SamplingParams(
            max_new_tokens=3, temperature=0.8, top_k=20, top_p=0.95, seed=3))
        eng.run()
        assert [r.output_tokens for r in reqs] == alone
        assert len(hot.output_tokens) == 3
        step = eng.stats()["perf"]["decode_step"]
        # two of the second run's five decode steps held the sampling row
        assert step["steps"] == 10
        assert step["sampled_step_share"] == pytest.approx(2 / 10)
        assert eng.stats()["decode_traces"] == 1
        eng.close()

    @pytest.mark.parametrize("vocab", [32768, 100352])
    def test_the_sampler_reads_logits_of_the_models_precision(self, v5e_chip,
                                                              vocab):
        """The decode step's last lines at the serve cells' widths (32 rows,
        a bf16 head), compiled for a described v5e chip: the head's product
        leaves its fusion in bf16 and the cast to float32 is an operation of
        its own. Without the sampler's barrier XLA fuses the cast into the
        product (its one consumer, now that a greedy batch sorts nothing)
        and the product hands on its float32 accumulator: bf16 logits that
        tie no longer tie, and greedy streams leave the parent's after some
        tokens (PERF.md Findings, PR 33)."""
        import re

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

        def tail_of_decode(x, head, temps, top_ks, top_ps, seeds, step_idx):
            logits = (x @ head)[:, None, :]
            keys = jax.vmap(
                lambda s, t: jax.random.fold_in(jax.random.PRNGKey(s), t)
            )(seeds, step_idx)
            return sample_logits(logits[:, -1], temps, top_ks, top_ps, keys)

        S, H = 32, 2048
        text = jax.jit(tail_of_decode).trace(
            sds((S, H), jnp.bfloat16), sds((H, vocab), jnp.bfloat16),
            sds((S,), jnp.float32), sds((S,), jnp.int32),
            sds((S,), jnp.float32), sds((S,), jnp.int32),
            sds((S,), jnp.int32)).lower(
            lowering_platforms=("tpu",)).compile().as_text()
        # the fused computation that holds the head's product, by its root
        products = [l for l in text.splitlines() if " convolution(" in l]
        assert len(products) == 1 and "ROOT" in products[0], products
        assert re.search(rf"= bf16\[{S},{vocab}\]", products[0]), products[0]
        assert re.search(rf"= f32\[{S},{vocab}\][^=]* convert\(", text)


# ---------------------------------------------------------------------------
# grouped matrix product of the sparse experts (compiled for the chip here,
# beside the other topology compiles; its numerics are in
# tests/test_moe_grouped_matmul.py)
# ---------------------------------------------------------------------------

class TestGroupedMatmulCompiles:
    # Laguna-XS.2: 256 experts, hidden 2048, expert width 512 (gate|up 1024)
    @pytest.mark.parametrize("rows", [256, 4096, 16384])   # decode, prefills
    @pytest.mark.parametrize("k,n", [(2048, 1024), (512, 2048)])
    def test_compiles_at_published_shapes_for_v5e(self, v5e_chip, rows, k, n):
        from paddle_tpu.kernels.moe_grouped_matmul import (
            moe_grouped_matmul_pallas)

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

        fn = jax.jit(lambda *a: moe_grouped_matmul_pallas(
            *a, interpret=False))
        lowered = fn.trace(
            sds((rows, k), jnp.bfloat16), sds((256, k, n), jnp.bfloat16),
            sds((256,), jnp.int32)).lower(lowering_platforms=("tpu",))
        text = lowered.as_text()
        assert sum("moe_grouped_matmul" in l and "tpu_custom_call" in l
                   for l in text.splitlines()) == 1
        lowered.compile()


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------

# name: (logits shape, temperature, top_k, top_p); scalars broadcast
_SAMPLER_BATCHES = {
    "all_greedy": ((4, 37), [0.0] * 4, 0, 1.0),
    "all_greedy_with_filters": ((4, 37), 0.0, [3, 0, 7, 1], 0.9),
    "all_sampling": ((4, 37), [0.7, 1.3, 0.2, 1.0], [0, 5, 0, 12],
                     [1.0, 0.9, 0.5, 0.95]),
    "all_sampling_scalars": ((3, 29), 0.8, 6, 0.9),
    "mixed": ((4, 37), [0.0, 0.9, 0.0, 1.4], [0, 4, 9, 0],
              [1.0, 1.0, 0.3, 0.8]),
    "mixed_one_sampling_row": ((5, 23), [0.0, 0.0, 0.0, 1.1, 0.0], 0, 1.0),
    "lone_row_greedy": ((1, 41), [0.0], 0, 1.0),
    "lone_row_sampling": ((1, 41), [0.6], 8, 0.9),
    "vector_greedy": ((41,), 0.0, 5, 0.9),
    "vector_sampling": ((41,), 0.9, 5, 0.9),
}


class TestSampleLogits:
    def test_temperature_zero_is_argmax(self):
        rng = np.random.RandomState(0)
        lg = jnp.asarray(rng.randn(5, 33).astype(np.float32))
        toks = sample_logits(lg, temperature=0.0, key=jax.random.PRNGKey(3))
        np.testing.assert_array_equal(np.asarray(toks),
                                      np.asarray(jnp.argmax(lg, -1)))
        # greedy needs no key at all
        toks2 = sample_logits(lg, temperature=0.0)
        np.testing.assert_array_equal(np.asarray(toks), np.asarray(toks2))

    def test_seeded_determinism(self):
        rng = np.random.RandomState(1)
        lg = jnp.asarray(rng.randn(4, 50).astype(np.float32))
        k = jax.random.PRNGKey(7)
        a = sample_logits(lg, 0.9, 10, 0.9, k)
        b = sample_logits(lg, 0.9, 10, 0.9, k)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        c = sample_logits(lg, 0.9, 10, 0.9, jax.random.PRNGKey(8))
        assert not np.array_equal(np.asarray(a), np.asarray(c))

    def test_top_k_restricts_support(self):
        rng = np.random.RandomState(2)
        lg = jnp.asarray(rng.randn(1, 40).astype(np.float32))
        top3 = set(np.asarray(jnp.argsort(lg[0])[-3:]).tolist())
        for s in range(20):
            t = int(sample_logits(lg, 1.5, 3, 1.0, jax.random.PRNGKey(s))[0])
            assert t in top3

    def test_top_p_keeps_nucleus_only(self):
        # one dominant token (p > 0.99): top_p=0.5 must always pick it
        lg = jnp.asarray(np.array([[10.0] + [0.0] * 9], np.float32))
        for s in range(10):
            t = int(sample_logits(lg, 1.0, 0, 0.5, jax.random.PRNGKey(s))[0])
            assert t == 0

    def test_per_row_keys_match_single_row_calls(self):
        """Batched sampling must equal row-by-row sampling with each row's
        own key — the property continuous batching relies on."""
        rng = np.random.RandomState(3)
        lg = jnp.asarray(rng.randn(3, 25).astype(np.float32))
        keys = jnp.stack([jax.random.PRNGKey(i) for i in (5, 6, 7)])
        batched = np.asarray(sample_logits(lg, 0.8, 5, 0.95, keys))
        for i in range(3):
            single = int(sample_logits(lg[i], 0.8, 5, 0.95, keys[i]))
            assert batched[i] == single

    # -- the conditional (PR 33): the tokens of the unconditional form --
    @pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
    @pytest.mark.parametrize("batch,keys", [
        (b, k) for b, (shape, *_) in _SAMPLER_BATCHES.items()
        for k in ("stacked", "single")
        if len(shape) == 2 or k == "single"])  # a vector takes one key
    def test_same_tokens_as_the_unconditional_form(self, batch, keys, jitted):
        """The conditional decides whether the sorts and the draw run,
        never a token: all-greedy, all-sampling or mixed, per-row or scalar
        parameters, stacked keys or one, a vector or a matrix of logits,
        the ids are those of the form that sorted and drew for every row
        and chose with ``where`` at the end."""
        shape, temps, top_k, top_p = _SAMPLER_BATCHES[batch]
        rng = np.random.RandomState(sum(map(ord, batch)))
        lg = jnp.asarray(rng.randn(*shape).astype(np.float32) * 3)
        key = (jnp.stack([jax.random.PRNGKey(11 + i)
                          for i in range(shape[0])])
               if keys == "stacked" else jax.random.PRNGKey(5))
        args = (lg, jnp.asarray(temps, jnp.float32),
                jnp.asarray(top_k, jnp.int32),
                jnp.asarray(top_p, jnp.float32), key)
        new, old = sample_logits, _sample_logits_unconditional
        if jitted:
            new, old = jax.jit(new), jax.jit(old)
        got, want = new(*args), old(*args)
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("keys", ["stacked", "single"])
    def test_sampling_work_only_inside_one_cond(self, keys):
        """With a key the jaxpr holds one ``cond``; both sorts, the
        cumulative sum and every random bit (a single key's split too) are
        in its taken branch, the other hands back the argmax computed
        outside, and nothing of the kind is left at top level, where it
        ran for every greedy batch before."""
        lg = jnp.zeros((4, 37), jnp.float32)
        key = (jnp.stack([jax.random.PRNGKey(i) for i in range(4)])
               if keys == "stacked" else jax.random.PRNGKey(0))
        jaxpr = jax.make_jaxpr(sample_logits)(
            lg, jnp.zeros(4), jnp.zeros(4, jnp.int32), jnp.ones(4), key).jaxpr

        def names(eqns):
            """Every primitive of ``eqns``, nested programs included."""
            out = []
            for eqn in eqns:
                out.append(eqn.primitive.name)
                for v in eqn.params.values():
                    for j in (v if isinstance(v, (tuple, list)) else (v,)):
                        j = getattr(j, "jaxpr", j)
                        if hasattr(j, "eqns"):
                            out.extend(names(j.eqns))
            return out

        def sampling_work(ns):
            return {n for n in ns if n in ("sort", "cumsum")
                    or n.startswith(("random_", "threefry"))}

        conds = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
        assert len(conds) == 1
        outside = names(e for e in jaxpr.eqns if e is not conds[0])
        assert not sampling_work(outside) and "argmax" in outside
        greedy_branch, sampling_branch = (
            names(b.jaxpr.eqns) for b in conds[0].params["branches"])
        assert greedy_branch == []                  # returns its operand
        assert sampling_branch.count("sort") == 2
        assert {"sort", "cumsum"} < sampling_work(sampling_branch)


def _sample_logits_unconditional(logits, temperature=1.0, top_k=0, top_p=1.0,
                                 key=None):
    """``sample_logits`` with a key as it was before PR 33: sorts, softmax,
    cumulative sum and draw for every row of every batch, and a ``where``
    at the end. The function must return these tokens, bit for bit."""
    squeeze = logits.ndim == 1
    lg = (logits[None] if squeeze else logits).astype(jnp.float32)
    B, V = lg.shape
    temp = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), (B,))
    tk = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), (B,))
    tp = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32), (B,))
    greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    key = jnp.asarray(key)
    if key.ndim == 2:
        keys = key
    elif B == 1:
        keys = key[None]
    else:
        keys = jax.random.split(key, B)
    desc = jnp.sort(lg, axis=-1)[:, ::-1]
    k_eff = jnp.clip(jnp.where(tk <= 0, V, tk), 1, V)
    kth = jnp.take_along_axis(desc, (k_eff - 1)[:, None], axis=-1)
    masked = jnp.where(lg >= kth, lg, -jnp.inf)
    probs = jax.nn.softmax(masked, axis=-1)
    sp = jnp.sort(probs, axis=-1)[:, ::-1]
    csum = jnp.cumsum(sp, axis=-1)
    first = jnp.arange(V, dtype=jnp.int32)[None] == 0
    keep = ((csum - sp) < tp[:, None]) | first
    thresh = jnp.min(jnp.where(keep, sp, jnp.inf), axis=-1, keepdims=True)
    masked = jnp.where(probs >= thresh, masked, -jnp.inf)
    scaled = masked / jnp.maximum(temp, 1e-6)[:, None]
    u = jax.vmap(lambda kk: jax.random.uniform(
        kk, (V,), minval=1e-20, maxval=1.0))(keys)
    sampled = jnp.argmax(scaled - jnp.log(-jnp.log(u)),
                         axis=-1).astype(jnp.int32)
    tok = jnp.where(temp > 0, sampled, greedy)
    return tok[0] if squeeze else tok


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

class TestEngine:
    def test_smoke_two_overlapping_requests(self):
        model = _tiny_model()
        eng = LLMEngine(model, block_size=8, max_slots=2, max_model_len=64)
        rng = np.random.RandomState(0)
        sp = SamplingParams(max_new_tokens=4)
        r1 = eng.add_request(list(rng.randint(0, 61, 5)), sp)
        r2 = eng.add_request(list(rng.randint(0, 61, 11)), sp)
        eng.run()
        assert len(r1.output_tokens) == 4 and len(r2.output_tokens) == 4
        assert r1.state.value == "finished" and r2.state.value == "finished"
        assert eng.stats()["blocks_used"] == 0  # everything returned

    def test_e2e_continuous_batching_matches_uncached(self):
        """ISSUE acceptance: >=4 concurrent requests, different prompt
        lengths, token-for-token equal to independent uncached greedy
        decode; pool high-water under the pool size; decode compiled
        exactly once."""
        model = _tiny_model()
        rng = np.random.RandomState(1)
        prompts = [list(rng.randint(0, 61, n)) for n in (3, 9, 17, 6)]
        sp = SamplingParams(max_new_tokens=6, temperature=0.0)
        eng = LLMEngine(model, block_size=8, max_slots=4, max_model_len=64)
        outs = eng.generate(prompts, sp)
        refs = [naive_generate(model, p, sp) for p in prompts]
        assert outs == refs
        st = eng.stats()
        assert st["decode_traces"] == 1
        assert st["block_high_water"] <= eng.cache.allocator.num_usable
        assert st["total_generated_tokens"] == 24
        assert st["mean_ttft"] is not None and st["tokens_per_sec"] > 0

    def test_no_retrace_across_varying_lengths(self):
        """Three-plus decode steps with different live sequence lengths and
        changing slot occupancy: exactly one decode trace (the paged cache
        keeps every step's shapes static)."""
        model = _tiny_model()
        eng = LLMEngine(model, block_size=4, max_slots=3, max_model_len=32)
        rng = np.random.RandomState(2)
        for n, new in ((2, 5), (7, 3), (12, 6)):
            eng.add_request(list(rng.randint(0, 61, n)),
                            SamplingParams(max_new_tokens=new))
        steps = 0
        while eng.step():
            steps += 1
        assert steps >= 3
        assert eng.decode_traces == 1
        # prefill buckets retrace per padded size only
        assert all(v == 1 for v in eng.prefill_traces.values())

    def test_preemption_requeue_and_parity(self):
        """Pool too small for three growing sequences: at least one request
        is preempted, re-queued, re-prefilled — and every output still
        matches the uncached reference exactly."""
        model = _tiny_model()
        rng = np.random.RandomState(3)
        prompts = [list(rng.randint(0, 61, n)) for n in (10, 9, 11)]
        sp = SamplingParams(max_new_tokens=12, temperature=0.0)
        eng = LLMEngine(model, block_size=4, num_blocks=9, max_slots=3,
                        max_model_len=32)
        outs = eng.generate(prompts, sp)
        st = eng.stats()
        assert st["num_preemptions"] > 0
        assert st["block_high_water"] <= 8
        refs = [naive_generate(model, p, sp) for p in prompts]
        assert outs == refs

    def test_seeded_sampling_independent_of_batching(self):
        """Sampled (non-greedy) streams are keyed per (request, index):
        batched + preempted execution reproduces solo decoding."""
        model = _tiny_model()
        rng = np.random.RandomState(4)
        prompts = [list(rng.randint(0, 61, n)) for n in (10, 9, 11)]
        sp = SamplingParams(max_new_tokens=8, temperature=0.8, top_k=20,
                            top_p=0.9, seed=7)
        eng = LLMEngine(model, block_size=4, num_blocks=9, max_slots=3,
                        max_model_len=32)
        outs = eng.generate(prompts, sp)
        refs = [naive_generate(model, p, sp) for p in prompts]
        assert outs == refs

    def test_streaming_and_queueing_beyond_slots(self):
        """More requests than slots: later ones wait, then join as slots
        free (join-on-finish); streaming yields tokens incrementally."""
        model = _tiny_model()
        rng = np.random.RandomState(5)
        eng = LLMEngine(model, block_size=8, max_slots=2, max_model_len=64)
        sp = SamplingParams(max_new_tokens=3)
        others = [eng.add_request(list(rng.randint(0, 61, 4)), sp)
                  for _ in range(3)]
        got = list(eng.stream(list(rng.randint(0, 61, 6)), sp))
        assert len(got) == 3
        assert all(len(r.output_tokens) == 3 for r in others)

    def test_streaming_callback(self):
        model = _tiny_model()
        seen = []
        eng = LLMEngine(model, block_size=8, max_slots=2, max_model_len=64)
        req = eng.add_request([1, 2, 3], SamplingParams(max_new_tokens=4),
                              on_token=lambda r, t: seen.append(t))
        eng.run()
        assert seen == req.output_tokens and len(seen) == 4

    def test_eos_stops_early(self):
        model = _tiny_model()
        # run greedy once, take as eos the first generated token past the
        # first that differs from all before it (random weights may repeat
        # a token), and expect a "stop" finish exactly where it appears
        full = naive_generate(model, [5, 4, 3],
                              SamplingParams(max_new_tokens=8))
        stop = next(i for i in range(1, len(full))
                    if full[i] not in full[:i])
        eng = LLMEngine(model, block_size=8, max_slots=1, max_model_len=64,
                        eos_token_id=full[stop])
        req = eng.add_request([5, 4, 3], SamplingParams(max_new_tokens=8))
        eng.run()
        assert req.output_tokens == full[:stop + 1]
        assert req.finish_reason == "stop"

    def test_request_validation(self):
        model = _tiny_model()
        eng = LLMEngine(model, block_size=8, max_slots=2, max_model_len=16)
        with pytest.raises(ValueError, match="max_model_len"):
            eng.add_request(list(range(14)), SamplingParams(max_new_tokens=8))
        with pytest.raises(ValueError, match="cannot hold"):
            LLMEngine(model, block_size=8, num_blocks=2, max_slots=1,
                      max_model_len=64)


@pytest.mark.slow
def test_serving_soak_many_requests_tiny_pool():
    """Long-horizon soak: a dozen mixed greedy/sampled requests through a
    pool sized to force sustained preemption churn; every stream must match
    its solo reference and the engine must drain completely."""
    model = _tiny_model(layers=2)
    rng = np.random.RandomState(6)
    prompts = [list(rng.randint(0, 61, int(n)))
               for n in rng.randint(2, 14, 12)]
    sps = [SamplingParams(max_new_tokens=int(rng.randint(3, 10)),
                          temperature=0.0 if i % 2 else 0.7,
                          top_k=15, top_p=0.95, seed=i)
           for i in range(12)]
    eng = LLMEngine(model, block_size=4, num_blocks=9, max_slots=3,
                    max_model_len=32)
    outs = eng.generate(prompts, sps)
    refs = [naive_generate(model, p, sp) for p, sp in zip(prompts, sps)]
    assert outs == refs
    st = eng.stats()
    assert st["num_finished"] == 12
    assert st["blocks_used"] == 0
    assert st["decode_traces"] == 1
    assert st["block_high_water"] <= 8
