"""Ragged paged attention (TPU): decode attention over a paged KV cache.

The serving engine (paddle_tpu.serving) keeps every sequence's K/V in
fixed-size blocks of one preallocated pool
``[num_blocks, 2, kv_heads, block_size, head_dim]`` and hands each decode
slot a block table (pool indices) plus a context length. This kernel
computes, for one query token per slot,

    out[s] = softmax(q[s] @ K[s, :ctx[s]]^T) @ V[s, :ctx[s]]

where K/V are *gathered through the block table* — the ragged part: slots
have arbitrary context lengths but the kernel runs on one static grid
(Ragged Paged Attention, PAPERS.md).

TPU shape: grid (slots, kv_heads, max_blocks); the block tables and context
lengths ride in scalar-prefetch (``pltpu.PrefetchScalarGridSpec``) so the
K/V BlockSpec index maps dereference ``block_tables[s, j]`` to pick which
pool block to DMA next — the gather happens in the pipeline, not in the
kernel body. Streaming softmax (m, l, acc) carries across the inner
block-grid dimension in VMEM scratch, exactly like flash attention's inner
loop; blocks past the context frontier are skipped via ``pl.when``.

Selection policy (the flash_attention / rmsnorm idiom): the Pallas kernel
runs on real TPU; under ``JAX_PLATFORMS=cpu`` (tests) and inside the
``check_vma`` interpreter the pure-jnp mirror below runs instead — the same
math unblocked, so CPU tests are authoritative for the semantics.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode as _interpret_mode, x64_off

__all__ = ["paged_attention", "paged_attention_pallas", "paged_attention_ref"]

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# jnp mirror (authoritative semantics; runs on CPU / under check_vma)
# ---------------------------------------------------------------------------

def paged_attention_ref(q, kv_pool, block_tables, context_lens, *,
                        sm_scale=None):
    """Pure-jnp ragged paged attention.

    q:            [slots, num_q_heads, head_dim] — one query token per slot
    kv_pool:      [num_blocks, 2, kv_heads, block_size, head_dim]
    block_tables: int32 [slots, max_blocks] pool indices per slot
    context_lens: int32 [slots] valid tokens per slot (including the token
                  whose K/V was just written); positions >= ctx are masked
    returns       [slots, num_q_heads, head_dim]
    """
    S, Hq, D = q.shape
    _, _, Hkv, bs, _ = kv_pool.shape
    M = block_tables.shape[1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    rep = Hq // Hkv

    # gather the slot's pages: [S, M, 2, Hkv, bs, D] -> [S, Hkv, M*bs, D]
    pages = kv_pool[block_tables]
    k = pages[:, :, 0].transpose(0, 2, 1, 3, 4).reshape(S, Hkv, M * bs, D)
    v = pages[:, :, 1].transpose(0, 2, 1, 3, 4).reshape(S, Hkv, M * bs, D)

    qg = (q.astype(jnp.float32) * scale).reshape(S, Hkv, rep, D)
    logits = jnp.einsum("shrd,shtd->shrt", qg, k.astype(jnp.float32))
    pos = jnp.arange(M * bs, dtype=jnp.int32)
    valid = pos[None, :] < context_lens[:, None].astype(jnp.int32)
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("shrt,shtd->shrd", probs, v.astype(jnp.float32))
    return out.reshape(S, Hq, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _paged_kernel(bt_ref, ctx_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, block_size, sm_scale, max_blocks):
    """Grid (slots, kv_heads, max_blocks); scalar-prefetch refs first.

    q_ref: [1, 1, rep, D] — this kv head's query rows for slot s
    k_ref/v_ref: [1, 1, 1, bs, D] — pool block bt[s, j] for this head
    o_ref: [1, 1, rep, D]; m/l/acc: VMEM scratch carried across j.
    """
    s = pl.program_id(0)
    j = pl.program_id(2)
    ctx = ctx_ref[s]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # blocks entirely past the context frontier contribute nothing
    @pl.when(j * block_size < ctx)
    def _attend():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale       # [rep, D]
        k = k_ref[0, 0, 0].astype(jnp.float32)               # [bs, D]
        v = v_ref[0, 0, 0].astype(jnp.float32)
        # explicit DEFAULT: the package-wide tensorfloat32 default would
        # ask Mosaic for Precision.HIGH, which it does not lower
        s_blk = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)             # [rep, bs]
        pos = j * jnp.int32(block_size) + jax.lax.broadcasted_iota(
            jnp.int32, s_blk.shape, 1)
        s_blk = jnp.where(pos < ctx, s_blk, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s_blk, axis=1, keepdims=True))
        p = jnp.exp(s_blk - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)

    @pl.when(j == max_blocks - 1)
    def _finalize():
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def paged_attention_pallas(q, kv_pool, block_tables, context_lens, *,
                           sm_scale=None, interpret=None):
    """Pallas ragged paged attention; see :func:`paged_attention_ref` for
    the argument contract. ``interpret`` defaults to the platform policy."""
    S, Hq, D = q.shape
    N, _, Hkv, bs, _ = kv_pool.shape
    M = block_tables.shape[1]
    rep = Hq // Hkv
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    # this body runs at TRACE time (the args are tracers inside the engine's
    # jitted step), so one record here is one Pallas kernel build — the
    # CompileWatcher's "kernel build" jit entry point
    from ..telemetry import perf as _perf

    _perf.compile_watcher().record_call(
        "pallas.paged_attention",
        _perf.abstract_signature(
            (q, kv_pool, block_tables, context_lens),
            ("q", "kv_pool", "block_tables", "context_lens")))
    if interpret is None:
        interpret = _interpret_mode()
    bt = block_tables.astype(jnp.int32)
    ctx = context_lens.astype(jnp.int32)
    # [S, Hkv, rep, D]: a (rep, D) block is then the full extent of the
    # last two dims, which Mosaic tiles for any rep (a (1, rep, D) block
    # over [S, Hq, D] is neither a multiple of 8 rows nor the full dim)
    q4 = q.reshape(S, Hkv, rep, D)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block_tables, context_lens
        grid=(S, Hkv, M),
        in_specs=[
            # this slot's query rows for kv head h: rows [h*rep, (h+1)*rep)
            pl.BlockSpec((1, 1, rep, D),
                         lambda s, h, j, bt, ctx: (s, h, 0, 0)),
            # K / V pool block bt[s, j] for head h (same pool array twice)
            pl.BlockSpec((1, 1, 1, bs, D),
                         lambda s, h, j, bt, ctx: (bt[s, j], 0, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, bs, D),
                         lambda s, h, j, bt, ctx: (bt[s, j], 1, h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, D),
                               lambda s, h, j, bt, ctx: (s, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rep, 1), jnp.float32),   # m
            pltpu.VMEM((rep, 1), jnp.float32),   # l
            pltpu.VMEM((rep, D), jnp.float32),   # acc
        ],
    )
    kern = functools.partial(_paged_kernel, block_size=bs, sm_scale=scale,
                             max_blocks=M)
    with x64_off():
        out = pl.pallas_call(
            kern,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((S, Hkv, rep, D), q.dtype),
            interpret=interpret,
            name="paged_attention",
        )(bt, ctx, q4, kv_pool, kv_pool)
    return out.reshape(S, Hq, D)


def paged_attention(q, kv_pool, block_tables, context_lens, *, sm_scale=None):
    """Policy entry: Pallas on TPU, jnp mirror elsewhere (the jnp path is
    also what runs inside the check_vma interpreter, where interpret-mode
    pallas cannot trace — same policy as kernels/flash_attention.py)."""
    from . import paged_attention_impl

    impl = paged_attention_impl()
    return impl(q, kv_pool, block_tables, context_lens, sm_scale=sm_scale)
