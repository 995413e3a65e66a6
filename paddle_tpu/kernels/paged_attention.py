"""Ragged paged attention (TPU): the decode step of one layer over a paged
KV cache, the new token's K/V written in place.

The serving engine (paddle_tpu.serving) keeps every sequence's K/V in
fixed-size blocks of one preallocated pool
``[layers, num_blocks, 2, kv_heads, block_size, head_dim]`` and hands each
decode slot a block table (pool indices) plus a context length. For one
query token per slot, :func:`paged_decode_pallas` first puts the token's own
K/V at position ``ctx[s] - 1`` of layer ``layer_idx`` and then computes

    out[s] = softmax(q[s] @ K[s, :ctx[s]]^T) @ V[s, :ctx[s]]

where K/V are *gathered through the block table* — the ragged part: slots
have arbitrary context lengths but the kernel is one compiled program
(Ragged Paged Attention, PAPERS.md). It returns the output and the pool.
:func:`paged_attention_pallas` is the same kernel body without the write,
over one layer's ``[num_blocks, 2, kv_heads, block_size, head_dim]``.

TPU shape: the grid is the slots; the block tables and context lengths ride
in scalar prefetch (``pltpu.PrefetchScalarGridSpec``) and the pool stays in
HBM. Inside a slot the kernel walks only the slot's live pages,
``cdiv(context_lens[s], block_size)`` of them, read at run time: a loop of
compute steps, each over ``_pages_per_step`` pages (at least a lane width of
tokens). A page is fetched by one async copy as the pool keeps it, the
contiguous ``[2, kv_heads, block_size, head_dim]`` that holds K and V of all
KV heads, into one of two VMEM landing buffers; while one buffer is attended
over, the next step's pages (the next slot's first, at a slot's end) are
already in flight into the other. The dots take K, V and the probabilities
in the pool's dtype (bf16 into the MXU) for all KV heads at once; the
streaming softmax (m, l, acc) is float32 and carries through the loop. So
the work follows the live K/V, not ``slots x kv_heads x max_blocks``: a slot
with one token costs one page.

The pool never moves. It is the call's operand whole, every layer of it, in
the row-major layout the engine holds it in, addressed at
``pool.at[layer_idx, block]``, and **aliased to the call's second output**
(``input_output_aliases``). A step that calls the kernel once a layer, each
call the only user of the pool the one before returned, and whose own pool
argument is donated, compiles to a chain of custom calls on one buffer: no
scatter, no slice of a layer, no relayout (a ``pool.at[...].set`` and a
``pool[layer_idx]`` around a read-only kernel cost two copies of the whole
pool and one of every layer a step, three quarters of the device's time;
PERF.md Findings PR 30; ``tests/test_serving.py`` holds the compiled
structure). The new row is written by the kernel itself: it lands in the
slot's last page in VMEM after that page's fetch, is attended over from
there, and the 64 KiB page goes back to the pool while the dots run (see
:func:`_paged_kernel` for why a page and not a row, and for the order).

Selection policy (the flash_attention / rmsnorm idiom): the Pallas kernel
runs on real TPU; under ``JAX_PLATFORMS=cpu`` (tests) and inside the
``check_vma`` interpreter the pure-jnp mirror below runs instead — the same
math unblocked, the write a ``pool.at[...].set``, so CPU tests are
authoritative for the semantics.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode as _interpret_mode, x64_off

__all__ = ["paged_attention_pallas", "paged_attention_ref",
           "paged_decode_pallas", "paged_decode_ref"]

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# jnp mirror (authoritative semantics; runs on CPU / under check_vma)
# ---------------------------------------------------------------------------

def paged_attention_ref(q, kv_pool, block_tables, context_lens, *,
                        sm_scale=None, window=None):
    """Pure-jnp ragged paged attention.

    q:            [slots, num_q_heads, head_dim] — one query token per slot
    kv_pool:      [num_blocks, 2, kv_heads, block_size, head_dim]
    block_tables: int32 [slots, max_blocks] pool indices per slot
    context_lens: int32 [slots] valid tokens per slot (including the token
                  whose K/V was just written); positions >= ctx are masked
    window:       None, or the static number of latest positions a query
                  sees (itself included): positions < ctx - window are
                  masked too
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
    ctx = context_lens[:, None].astype(jnp.int32)
    valid = pos[None, :] < ctx
    if window is not None:
        valid &= pos[None, :] >= ctx - window
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("shrt,shtd->shrd", probs, v.astype(jnp.float32))
    return out.reshape(S, Hq, D).astype(q.dtype)


def paged_decode_ref(q, k_new, v_new, kv_pool, block_tables, context_lens, *,
                     layer_idx, sm_scale=None, window=None):
    """Pure-jnp decode step of one layer: write the slots' new K/V, attend.

    k_new, v_new: [slots, kv_heads, head_dim] — K/V of each slot's query
                  token, position ``context_lens - 1``
    kv_pool:      [layers, num_blocks, 2, kv_heads, block_size, head_dim]
    layer_idx:    the layer of the pool this call writes and reads
    (the rest as :func:`paged_attention_ref`)
    returns       (out [slots, num_q_heads, head_dim], the pool with row
                  ``(pos % block_size)`` of block ``block_tables[s, pos //
                  block_size]`` of ``layer_idx`` replaced, nothing else)
    """
    bs = kv_pool.shape[4]
    pos = context_lens.astype(jnp.int32) - 1
    rows = jnp.arange(q.shape[0], dtype=jnp.int32)
    bidx = block_tables[rows, pos // bs]                 # [S]
    off = pos % bs
    # mixed basic/advanced indexing: advanced dims (S) move to the front,
    # so the target of the .set is [S, kv_heads, head_dim]
    pool = kv_pool.at[layer_idx, bidx, 0, :, off, :].set(
        k_new.astype(kv_pool.dtype))
    pool = pool.at[layer_idx, bidx, 1, :, off, :].set(
        v_new.astype(kv_pool.dtype))
    out = paged_attention_ref(q, pool[layer_idx], block_tables, context_lens,
                              sm_scale=sm_scale, window=window)
    return out, pool


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

# The least KV length of one compute step: a lane width, so the score tile
# [rep, T] fills whole vregs and the dots have MXU-sized operands.
_MIN_STEP_TOKENS = 128
# What the two K/V landing buffers may take of VMEM. Well under the 16 MB a
# v5e core scopes to one kernel: the score and probability tiles, the
# pipelined q/out blocks and Mosaic's own temporaries share the rest.
_KV_BUFFER_BYTES = 4 * 1024 * 1024


def _pages_per_step(block_size, kv_heads, head_dim, itemsize, max_blocks):
    """How many pool blocks one compute step attends over, from the shapes
    alone: enough for ``_MIN_STEP_TOKENS`` tokens, no more than the table
    holds or than two buffers of them fit in ``_KV_BUFFER_BYTES``."""
    page_bytes = 2 * kv_heads * block_size * head_dim * itemsize
    fit = max(1, _KV_BUFFER_BYTES // (2 * page_bytes))
    want = pl.cdiv(_MIN_STEP_TOKENS, block_size)
    return min(want, fit, max_blocks)


def _paged_kernel(bt_ref, ctx_ref, *refs, sm_scale, window, write):
    """Grid (slots,); scalar-prefetch refs first.

    bt_ref [S, M], ctx_ref [S]: SMEM. q_ref/o_ref: [1, Hkv, rep, D], this
    slot's rows. pool_hbm: the whole pool, left in HBM. kv_buf:
    [2, 2, Hkv, P, bs, D] — two landing buffers of P pages, K and V of
    every KV head. sems: one DMA semaphore a buffer and one for the
    write-back. buf_ref: SMEM [1], the buffer the *next* compute step reads
    (carried across grid steps).

    With ``write`` the pool is ``[L, N, ...]`` and is this call's aliased
    output: ``refs`` is (layer_ref, q_ref, new_ref, the pool as input,
    o_ref, the pool as output, scratch). layer_ref, SMEM [1], is a third
    prefetched scalar: the layer is data, so that the calls of a step's
    layers are one kernel, traced, compiled and loaded once (a static index
    made eight, and 3 s more set-up; PERF.md Findings PR 30). Every read
    and the write go through the output ref, which is the same HBM on the
    chip and the one coherent copy in the interpreter. new_ref
    ``[1, 2, Hkv, 1, D]`` is this slot's new K/V row, position
    ``ctx - 1``. Its page is the slot's last, fetched (a pair
    ahead, so before any write) without the row: after the wait of the
    slot's last step the row is put into the landing buffer, by a select
    over the page's rows (one row at a run-time offset is half a packed
    bf16 sublane, which neither a store nor a DMA addresses), the dots read
    it from there, and the page goes back to the pool while they run. No
    other slot reads that page (a shared block is never written:
    ``ensure_writable``), except the inactive slots, which all carry the
    zero table, write the reserved block 0 and give an output nobody reads.

    The work is the flat sequence of (slot, step) pairs with
    ``step < cdiv(pages(slot), P)``; each pair waits for its own pages and
    has already started the next pair's copies, across slot boundaries too,
    so only the very first copy of a call is exposed.

    With a ``window`` a slot's walk starts at the page that holds position
    ``ctx - window`` (``first_page``), in the compute step that page falls
    in; that step's older pages are not copied and the first page's older
    positions are masked. ``window`` is static: without one, none of this
    is traced.
    """
    if write:
        (layer_ref, q_ref, new_ref, _, o_ref, pool_hbm, kv_buf, sems,
         buf_ref) = refs
        pool_hbm = pool_hbm.at[layer_ref[0]]
    else:
        q_ref, pool_hbm, o_ref, kv_buf, sems, buf_ref = refs
    s = pl.program_id(0)
    num_slots = pl.num_programs(0)
    _, _, Hkv, P, bs, D = kv_buf.shape
    rep = q_ref.shape[2]
    T = P * bs
    max_blocks = bt_ref.shape[1]

    def live_pages(slot):
        # a slot always owns page 0 (ctx >= 1 by contract; ctx 0 would read
        # page 0 fully masked); never past the table
        return jnp.clip(pl.cdiv(ctx_ref[slot], bs), 1, max_blocks)

    def first_page(slot):
        return jnp.maximum(ctx_ref[slot] - window, 0) // bs

    def first_step(slot):
        return 0 if window is None else first_page(slot) // P

    def page_copy(slot, page, buf):
        """The copy of table entry ``page`` of ``slot`` into its place in
        landing buffer ``buf``."""
        return pltpu.make_async_copy(
            pool_hbm.at[bt_ref[slot, page]],
            kv_buf.at[buf, :, :, page % P],
            sems.at[buf])

    def for_each_live_page(slot, step, do):
        """``do(page)`` for each live page of compute step ``step``."""
        first = step * P
        jax.lax.fori_loop(
            first if window is None
            else jnp.maximum(first, first_page(slot)),
            jnp.minimum(first + P, live_pages(slot)),
            lambda page, _: do(page), None)

    def start(slot, step, buf):
        for_each_live_page(
            slot, step, lambda page: page_copy(slot, page, buf).start())

    def wait(slot, step, buf):
        for_each_live_page(
            slot, step, lambda page: page_copy(slot, page, buf).wait())

    @pl.when(s == 0)
    def _first():
        buf_ref[0] = 0
        start(0, first_step(0), 0)

    ctx = ctx_ref[s]
    n_pages = live_pages(s)
    new_page = n_pages - 1          # holds position ctx - 1, the new row's
    new_at = new_page % P           # its place in a landing buffer
    step0 = first_step(s)

    def write_back(buf):
        """The copy of the new row's page from landing buffer ``buf`` to
        its block of the pool."""
        return pltpu.make_async_copy(
            kv_buf.at[buf, :, :, new_at],
            pool_hbm.at[bt_ref[s, new_page]],
            sems.at[2])

    n_steps = pl.cdiv(n_pages, P)
    if window is not None:
        n_steps -= step0
    buf0 = buf_ref[0]
    # operands in the pool's dtype (bf16 stays bf16 into the MXU); the scale
    # is applied in f32 first, the statistics below stay f32
    q = (q_ref[0].astype(jnp.float32) * sm_scale).astype(kv_buf.dtype)

    def step_body(n, carry):
        m_prev, l_prev, acc = carry
        # the step's place in the table
        i = n if window is None else step0 + n
        buf = (buf0 + n) % 2
        last = n + 1 == n_steps
        nxt_slot = jnp.where(last, s + 1, s)
        # (past the last slot nothing is started: any slot's answer does)
        nxt_step = jnp.where(
            last, 0 if window is None
            else first_step(jnp.minimum(nxt_slot, num_slots - 1)), i + 1)

        @pl.when(nxt_slot < num_slots)
        def _prefetch():
            start(nxt_slot, nxt_step, 1 - buf)

        wait(s, i, buf)
        # pages of this step past the live ones were not copied: whatever
        # the buffer held there is masked out of the scores, but 0 * V must
        # stay finite, so their V is zeroed (no copy in flight targets them)
        def zero_v(p, _):
            kv_buf[buf, 1, :, p] = jnp.zeros((Hkv, bs, D), kv_buf.dtype)

        jax.lax.fori_loop(jnp.minimum(n_pages - i * P, P), P, zero_v, None)
        if window is not None:
            # nor were the first step's pages before the window's first
            jax.lax.fori_loop(0, jnp.clip(first_page(s) - i * P, 0, P),
                              zero_v, None)
        if write:
            @pl.when(last)
            def _write():
                page = kv_buf[buf, :, :, new_at]             # [2, Hkv, bs, D]
                row = jax.lax.broadcasted_iota(jnp.int32, page.shape, 2)
                kv_buf[buf, :, :, new_at] = jnp.where(
                    row == (ctx - 1) % bs, new_ref[0], page)
                write_back(buf).start()

        k = kv_buf[buf, 0].reshape(Hkv, T, D)
        v = kv_buf[buf, 1].reshape(Hkv, T, D)
        # explicit DEFAULT: the package-wide tensorfloat32 default would
        # ask Mosaic for Precision.HIGH, which it does not lower
        sc = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)             # [Hkv, rep, T]
        pos = i * T + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 2)
        seen = pos < ctx
        if window is not None:
            seen &= pos >= ctx - window
        sc = jnp.where(seen, sc, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=2, keepdims=True))
        p_blk = jnp.exp(sc - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p_blk, axis=2, keepdims=True)
        acc = alpha * acc + jax.lax.dot_general(
            p_blk.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)             # [Hkv, rep, D]
        return m_new, l_new, acc

    m0 = jnp.full((Hkv, rep, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((Hkv, rep, 1), jnp.float32)
    acc0 = jnp.zeros((Hkv, rep, D), jnp.float32)
    _, l_fin, acc = jax.lax.fori_loop(0, n_steps, step_body, (m0, l0, acc0))
    if write:
        # the next compute step starts copies into this buffer
        write_back((buf0 + n_steps - 1) % 2).wait()
    buf_ref[0] = (buf0 + n_steps) % 2
    o_ref[0] = (acc / jnp.maximum(l_fin, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("sm_scale", "window", "interpret"))
def _paged_call(q4, kv_new, layer, kv_pool, block_tables, context_lens, *,
                sm_scale, window, interpret):
    """The ``pallas_call`` on ``q4 [S, Hkv, rep, D]``. Jitted so that a step
    that calls it once a layer traces and lowers the kernel once: the
    layers' calls have the same shapes and share the one traced function
    (XLA inlines it; the device op is still ``paged_attention``).

    ``layer=None``: ``kv_pool`` is one layer's ``[N, 2, Hkv, bs, D]``, read
    only (``kv_new`` is None); returns the output. Else ``layer`` is int32
    ``[1]``, ``kv_pool`` ``[L, N, 2, Hkv, bs, D]``, ``kv_new
    [S, 2, Hkv, 1, D]`` the slots' new rows; returns (output, pool), the
    pool aliased to the operand."""
    S, Hkv, rep, D = q4.shape
    bs = kv_pool.shape[-2]
    P = _pages_per_step(bs, Hkv, D, kv_pool.dtype.itemsize,
                        block_tables.shape[1])
    write = layer is not None

    # the pool stays in HBM; the kernel copies the pages it needs
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    q_spec = pl.BlockSpec((1, Hkv, rep, D), lambda s, *_: (s, 0, 0, 0))
    scalars = [block_tables.astype(jnp.int32), context_lens.astype(jnp.int32)]
    operands, in_specs = [q4, kv_pool], [q_spec, in_hbm]
    out_specs = q_spec
    out_shape = jax.ShapeDtypeStruct(q4.shape, q4.dtype)
    if write:
        scalars.append(layer)
        # a row as [2, Hkv, 1, D]: the block's last two dims are whole, and
        # the row broadcasts over a page's rows along the sublanes
        operands.insert(1, kv_new)
        in_specs.insert(1, pl.BlockSpec(
            (1, 2, Hkv, 1, D), lambda s, *_: (s, 0, 0, 0, 0)))
        out_specs = (q_spec, in_hbm)
        out_shape = (out_shape,
                     jax.ShapeDtypeStruct(kv_pool.shape, kv_pool.dtype))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(S,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((2, 2, Hkv, P, bs, D), kv_pool.dtype),
            pltpu.SemaphoreType.DMA((3,)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    with x64_off():
        return pl.pallas_call(
            functools.partial(_paged_kernel, sm_scale=sm_scale,
                              window=window, write=write),
            grid_spec=grid_spec,
            out_shape=out_shape,
            # the last operand is the pool: written in place, handed on as
            # output 1
            input_output_aliases=(
                {len(scalars) + len(operands) - 1: 1} if write else {}),
            # slots run in order: the landing buffers and the buffer index
            # carry from one slot to the next
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
            name="paged_attention",
        )(*scalars, *operands)


def _pallas(q, kv_new, kv_pool, block_tables, context_lens, *, layer_idx,
            sm_scale, window, interpret, **recorded):
    """What both entries do around :func:`_paged_call`. They run at TRACE
    time (their args are tracers inside the engine's jitted step), so one
    record here is one Pallas kernel build — the CompileWatcher's "kernel
    build" jit entry point."""
    from ..telemetry import perf as _perf

    recorded.update(q=q, kv_pool=kv_pool, block_tables=block_tables,
                    context_lens=context_lens)
    _perf.compile_watcher().record_call(
        "pallas.paged_attention",
        _perf.abstract_signature(tuple(recorded.values()), tuple(recorded)))
    S, Hq, D = q.shape
    Hkv = kv_pool.shape[-3]
    if interpret is None:
        interpret = _interpret_mode()
    # [S, Hkv, rep, D]: a (Hkv, rep, D) block is then the full extent of
    # the last two dims, which Mosaic tiles for any rep (a block of rep rows
    # of [S, Hq, D] is neither a multiple of 8 rows nor the full dim)
    got = _paged_call(
        q.reshape(S, Hkv, Hq // Hkv, D), kv_new,
        None if layer_idx is None else jnp.full((1,), layer_idx, jnp.int32),
        kv_pool, block_tables, context_lens, window=window,
        interpret=interpret,
        sm_scale=sm_scale if sm_scale is not None else 1.0 / math.sqrt(D))
    if layer_idx is None:
        return got.reshape(S, Hq, D)
    return got[0].reshape(S, Hq, D), got[1]


def paged_attention_pallas(q, kv_pool, block_tables, context_lens, *,
                           sm_scale=None, window=None, interpret=None):
    """Pallas ragged paged attention over one layer's pool, read only; see
    :func:`paged_attention_ref` for the argument contract. ``interpret``
    defaults to the platform policy."""
    return _pallas(q, None, kv_pool, block_tables, context_lens,
                   layer_idx=None, sm_scale=sm_scale, window=window,
                   interpret=interpret)


def paged_decode_pallas(q, k_new, v_new, kv_pool, block_tables, context_lens,
                        *, layer_idx, sm_scale=None, window=None,
                        interpret=None):
    """Pallas decode step of one layer, in place; see
    :func:`paged_decode_ref` for the argument contract. The returned pool
    is the operand's buffer (``input_output_aliases``): a caller that owns
    it (the engine donates it to the step) pays no copy."""
    kv_new = jnp.stack([k_new, v_new], axis=1)[:, :, :, None, :]
    return _pallas(q, kv_new.astype(kv_pool.dtype), kv_pool, block_tables,
                   context_lens, layer_idx=layer_idx, sm_scale=sm_scale,
                   window=window, interpret=interpret,
                   k_new=k_new, v_new=v_new)
