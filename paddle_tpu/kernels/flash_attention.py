"""Pallas flash attention (TPU): causal, varlen/segment, masked, dropout.

Replaces the reference's vendored CUDA flash-attn
(/root/reference/third_party/flashattn, kernels
 paddle/phi/kernels/gpu/flash_attn_kernel.cu, python API
 python/paddle/nn/functional/flash_attention.py, varlen entry
 python/paddle/nn/functional/flash_attention.py:272 flash_attn_unpadded)
with a TPU-native tiled online-softmax kernel family. One parameterized
kernel covers four capabilities, composable:

- **causal**: block-skipped lower-triangular masking (blocks beyond the
  causal frontier are never read).
- **segments** (varlen / padding): int32 segment ids for q and k; scores
  where ``qseg != kseg`` are masked, and per-q-block [lo, hi) kv-block
  ranges computed host-side via searchsorted (splash-style block skipping)
  bound the inner loop, so cross-sequence blocks of a packed batch are
  skipped, not just masked. ``flash_attn_unpadded``'s cu_seqlens map to
  segment ids; padding masks map to a pad segment id.
- **dense mask**: an additive mask streamed through VMEM in blocks
  (never materializing scores), supporting [1|B|B*H, 1|Sq, Sk] shapes
  (bool masks become 0/-1e30 bf16; float masks stay f32).
- **dropout**: counter-based in-kernel PRNG (`pltpu.prng_seed` keyed on
  (seed, batch·head, q-block, k-block)), regenerated bit-identically in
  the backward kernels — no dropout mask is ever stored.

Backward is the FlashAttention-2 recomputation scheme (saved logsumexp +
delta) as two Pallas kernels, wired via jax.custom_vjp over the pair
``(out, lse)`` so ring attention can merge per-block results with the
online-softmax rule and still differentiate (the lse cotangent folds into
ds as ``p * g_lse``).

Layout: paddle's [B, S, H, D]; internally [B*H, S, D]. GQA handled by
repeating KV heads in the wrapper (dKV summed back by AD).
"""
from __future__ import annotations

import functools
import math
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from . import interpret_mode as _interpret_mode, x64_off

__all__ = ["flash_attention_pallas", "flash_attn_varlen_pallas"]

NEG_INF = -1e30
_VMEM_BUDGET = 12 * 1024 * 1024  # leave headroom below the 16MB/core VMEM


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _unpack(refs, *, has_seg, has_mask, has_drop, n_extra):
    """Split the flat pallas ref list into named groups.

    Input order: q, k, v, *extra (do/lse/delta/glse for bwd),
    [qseg, kseg, lob, hib], [mask], [seed]."""
    it = iter(refs)
    q, k, v = next(it), next(it), next(it)
    extra = [next(it) for _ in range(n_extra)]
    seg = (next(it), next(it), next(it), next(it)) if has_seg else None
    mask = next(it) if has_mask else None
    seed = next(it) if has_drop else None
    return q, k, v, extra, seg, mask, seed


def _tile_mask(s, *, causal, q_off, k_off, block_q, block_k,
               qseg=None, kseg=None, mask_blk=None):
    """Apply causal / segment / additive masks to a [block_q, block_k] tile."""
    if mask_blk is not None:
        s = s + mask_blk.astype(jnp.float32)
    if causal:
        q_ids = q_off + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_ids = k_off + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        s = jnp.where(q_ids >= k_ids, s, NEG_INF)
    if qseg is not None:
        s = jnp.where(qseg[:, None] == kseg[None, :], s, NEG_INF)
    return s


def _drop_thresh(dropout_p):
    # prng_random_bits yields SIGNED int32 uniform over the full range;
    # shifting the [0, 2^32) cut-point by -2^31 makes the signed compare
    # keep exactly (1 - p) of the mass.
    return jnp.int32(min(int(dropout_p * 2.0 ** 32), 2 ** 32 - 1) - 2 ** 31)


def _drop_mask(seed_ref, b, qi, ki, block_q, block_k, dropout_p):
    """Regenerable dropout multiplier for score tile (b, qi, ki):
    0 with prob p, 1/(1-p) otherwise."""
    # Mosaic accepts at most two seed words: mix (seed, batch·head) and
    # (q-block, k-block) — the same pair in fwd and both bwd kernels, so the
    # mask regenerates bit-identically without ever being stored.
    s0 = seed_ref[0] + b * jnp.int32(-1640531527)  # golden-ratio mix
    s1 = qi * jnp.int32(65536) + ki
    pltpu.prng_seed(s0, s1)
    bits = pltpu.prng_random_bits((block_q, block_k)).astype(jnp.int32)
    keep = (bits >= _drop_thresh(dropout_p)).astype(jnp.float32)
    return keep * (1.0 / (1.0 - dropout_p))


def _fwd_kernel(*refs, block_k, sm_scale, causal, seq_k, heads,
                has_seg, has_mask, mask_rows, dropout_p):
    q_ref, k_ref, v_ref, _, seg, mask_ref, seed_ref = _unpack(
        refs[:-2], has_seg=has_seg, has_mask=has_mask,
        has_drop=dropout_p > 0, n_extra=0)
    o_ref, lse_ref = refs[-2], refs[-1]
    block_q = q_ref.shape[1]
    d = q_ref.shape[2]
    b = pl.program_id(0)
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * sm_scale
    q_offset = qi * jnp.int32(block_q)

    if has_seg:
        qseg_ref, kseg_ref, lob_ref, hib_ref = seg
        bseg = b // jnp.int32(heads)
        lo = lob_ref[bseg, qi]
        hi = hib_ref[bseg, qi]
        qseg = qseg_ref[0]
    else:
        lo = jnp.int32(0)
        hi = jnp.int32(pl.cdiv(seq_k, block_k))
        if causal:
            hi = jnp.minimum(
                hi, (q_offset + jnp.int32(block_q + block_k - 1)) // jnp.int32(block_k))
        qseg = None

    m = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((block_q, 1), jnp.float32)
    acc = jnp.zeros((block_q, d), jnp.float32)

    def body(ki, carry):
        m, l, acc = carry
        k_off = ki * jnp.int32(block_k)
        k = k_ref[0, pl.ds(k_off, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(k_off, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)
        mask_blk = None
        if has_mask:
            mask_blk = mask_ref[0, :, pl.ds(k_off, block_k)]
        s = _tile_mask(s, causal=causal, q_off=q_offset, k_off=k_off,
                       block_q=block_q, block_k=block_k, qseg=qseg,
                       kseg=kseg_ref[0, pl.ds(k_off, block_k)] if has_seg else None,
                       mask_blk=mask_blk)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        if dropout_p > 0:
            p_acc = p * _drop_mask(seed_ref, b, qi, ki, block_q, block_k, dropout_p)
        else:
            p_acc = p
        acc_new = alpha * acc + jax.lax.dot_general(
            p_acc, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(lo, hi, body, (m, l, acc))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l_safe)


def _bwd_dq_kernel(*refs, block_k, sm_scale, causal, seq_k, heads,
                   has_seg, has_mask, mask_rows, dropout_p):
    (q_ref, k_ref, v_ref, (do_ref, lse_ref, delta_ref, glse_ref),
     seg, mask_ref, seed_ref) = _unpack(
        refs[:-1], has_seg=has_seg, has_mask=has_mask,
        has_drop=dropout_p > 0, n_extra=4)
    dq_ref = refs[-1]
    block_q, d = q_ref.shape[1], q_ref.shape[2]
    b = pl.program_id(0)
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0]
    delta = delta_ref[0]
    glse = glse_ref[0]
    q_offset = qi * jnp.int32(block_q)

    if has_seg:
        qseg_ref, kseg_ref, lob_ref, hib_ref = seg
        bseg = b // jnp.int32(heads)
        lo, hi = lob_ref[bseg, qi], hib_ref[bseg, qi]
        qseg = qseg_ref[0]
    else:
        lo = jnp.int32(0)
        hi = jnp.int32(pl.cdiv(seq_k, block_k))
        if causal:
            hi = jnp.minimum(
                hi, (q_offset + jnp.int32(block_q + block_k - 1)) // jnp.int32(block_k))
        qseg = None

    def body(ki, dq):
        k_off = ki * jnp.int32(block_k)
        k = k_ref[0, pl.ds(k_off, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(k_off, block_k), :].astype(jnp.float32)
        s = sm_scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)
        mask_blk = None
        if has_mask:
            mask_blk = mask_ref[0, :, pl.ds(k_off, block_k)]
        s = _tile_mask(s, causal=causal, q_off=q_offset, k_off=k_off,
                       block_q=block_q, block_k=block_k, qseg=qseg,
                       kseg=kseg_ref[0, pl.ds(k_off, block_k)] if has_seg else None,
                       mask_blk=mask_blk)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)
        if dropout_p > 0:
            dp = dp * _drop_mask(seed_ref, b, qi, ki, block_q, block_k, dropout_p)
        ds = p * (dp - delta + glse)
        return dq + sm_scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)

    dq = jax.lax.fori_loop(lo, hi, body, jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, block_q, sm_scale, causal, seq_q, heads,
                    has_seg, has_mask, mask_rows, dropout_p):
    (q_ref, k_ref, v_ref, (do_ref, lse_ref, delta_ref, glse_ref),
     seg, mask_ref, seed_ref) = _unpack(
        refs[:-2], has_seg=has_seg, has_mask=has_mask,
        has_drop=dropout_p > 0, n_extra=4)
    dk_ref, dv_ref = refs[-2], refs[-1]
    block_k, d = k_ref.shape[1], k_ref.shape[2]
    b = pl.program_id(0)
    ki = pl.program_id(1)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    k_offset = ki * jnp.int32(block_k)

    if has_seg:
        qseg_ref, kseg_ref, lob_ref, hib_ref = seg
        bseg = b // jnp.int32(heads)
        lo, hi = lob_ref[bseg, ki], hib_ref[bseg, ki]
        kseg = kseg_ref[0]
    else:
        lo = (k_offset // jnp.int32(block_q)) if causal else jnp.int32(0)
        hi = jnp.int32(pl.cdiv(seq_q, block_q))
        kseg = None

    def body(qi, carry):
        dk, dv = carry
        q_off = qi * jnp.int32(block_q)
        q = q_ref[0, pl.ds(q_off, block_q), :].astype(jnp.float32)
        do = do_ref[0, pl.ds(q_off, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(q_off, block_q), :]
        delta = delta_ref[0, pl.ds(q_off, block_q), :]
        glse = glse_ref[0, pl.ds(q_off, block_q), :]
        s = sm_scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)
        mask_blk = None
        if has_mask:
            rows = pl.ds(q_off, block_q) if mask_rows > 1 else slice(None)
            mask_blk = mask_ref[0, rows, :]
        s = _tile_mask(s, causal=causal, q_off=q_off, k_off=k_offset,
                       block_q=block_q, block_k=block_k,
                       qseg=qseg_ref[0, pl.ds(q_off, block_q)] if has_seg else None,
                       kseg=kseg, mask_blk=mask_blk)
        p = jnp.exp(s - lse)  # [bq, bk]
        if dropout_p > 0:
            dmask = _drop_mask(seed_ref, b, qi, ki, block_q, block_k, dropout_p)
            p_v = p * dmask
        else:
            dmask = None
            p_v = p
        dv_new = dv + jax.lax.dot_general(
            p_v, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)
        if dmask is not None:
            dp = dp * dmask
        ds = p * (dp - delta + glse)
        dk_new = dk + sm_scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)
        return dk_new, dv_new

    dk, dv = jax.lax.fori_loop(
        lo, hi, body,
        (jnp.zeros((block_k, d), jnp.float32), jnp.zeros((block_k, d), jnp.float32)))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# host-side helpers
# ---------------------------------------------------------------------------

def _out_vma(*examples):
    """Union of the inputs' varying-manual-axes sets.

    Inside a ``check_vma=True`` partial-manual shard_map (the pp pipeline),
    ``pallas_call`` out_shapes must declare which mesh axes the outputs vary
    over; outputs vary exactly over the union of the input vmas. Outside
    shard_map this is the empty frozenset, which is also valid.
    """
    vma = frozenset()
    for e in examples:
        if e is None:
            continue
        vma |= jax.typeof(e).vma
    return vma


def _use_jnp_mirror(vma, dropout_p=0.0, bq=128, bk=128) -> bool:
    """Interpret-mode pallas cannot trace inside a ``check_vma=True``
    shard_map (the HLO interpreter's internal dynamic_slice indices carry no
    vma) and has no PRNG lowering, so CPU tests of the sharded pipeline and
    of dropout run a jnp mirror of the exact kernel math instead. On TPU the
    real kernel runs everywhere except dropout at sub-(8,128) tiles."""
    interp = _interpret_mode()
    if interp and (bool(vma) or dropout_p > 0):
        return True
    if dropout_p > 0 and (bq % 8 or bk % 128):
        return True  # PRNG tile shape constraint
    return False


def _choose_blocks(seq_q, seq_k, max_b=512):
    bq = min(max_b, seq_q)
    while seq_q % bq:
        bq //= 2
    bk = min(max_b, seq_k)
    while seq_k % bk:
        bk //= 2
    return max(bq, 1), max(bk, 1)


def _fit_blocks(Sq, Sk, D, in_bytes, mask_bytes, has_seg):
    """Pick (bq, bk) so every kernel's VMEM residency fits the budget, or
    return None if even the smallest blocking cannot fit (caller falls back
    loudly to the XLA composition)."""
    for max_b in (512, 256, 128, 64):
        bq, bk = _choose_blocks(Sq, Sk, max_b)
        kv = 2 * Sk * D * in_bytes                     # fwd/dq hold K,V whole
        qdo = 2 * Sq * D * in_bytes                    # dkv holds Q,dO whole
        fwd = kv + 3 * bq * D * 4 + (bq * Sk * mask_bytes)
        dkv = qdo + 4 * bk * D * 4 + (Sq * bk * mask_bytes) + 3 * Sq * 4
        seg = (Sq + Sk) * 4 if has_seg else 0
        if max(fwd, dkv) + seg <= _VMEM_BUDGET:
            return bq, bk
    return None


def _varlen_bounds_q(qseg, kseg, bq, bk, causal):
    """Per-(batch, q-block) [lo, hi) kv-block ranges. Segment ids must be
    sorted along the sequence (contiguous packing — true for cu_seqlens
    layouts and padding masks)."""
    Bseg, Sq = qseg.shape
    nqb = Sq // bq
    qv = qseg.reshape(Bseg, nqb, bq)
    qmin, qmax = qv.min(-1), qv.max(-1)
    k_lo = jax.vmap(lambda ks, s: jnp.searchsorted(ks, s, side="left"))(kseg, qmin)
    k_hi = jax.vmap(lambda ks, s: jnp.searchsorted(ks, s, side="right"))(kseg, qmax)
    lob = (k_lo // bk).astype(jnp.int32)
    hib = (-(-k_hi // bk)).astype(jnp.int32)
    if causal:
        causal_hi = (jnp.arange(nqb, dtype=jnp.int32) * bq + bq + bk - 1) // bk
        hib = jnp.minimum(hib, causal_hi[None, :])
    return lob, jnp.maximum(hib, lob)


def _varlen_bounds_kv(qseg, kseg, bq, bk, causal):
    """Per-(batch, k-block) [lo, hi) q-block ranges for the dkv kernel."""
    Bseg, Sk = kseg.shape
    nkb = Sk // bk
    kv = kseg.reshape(Bseg, nkb, bk)
    kmin, kmax = kv.min(-1), kv.max(-1)
    q_lo = jax.vmap(lambda qs, s: jnp.searchsorted(qs, s, side="left"))(qseg, kmin)
    q_hi = jax.vmap(lambda qs, s: jnp.searchsorted(qs, s, side="right"))(qseg, kmax)
    lob = (q_lo // bq).astype(jnp.int32)
    hib = (-(-q_hi // bq)).astype(jnp.int32)
    if causal:
        causal_lo = (jnp.arange(nkb, dtype=jnp.int32) * bk) // bq
        lob = jnp.maximum(lob, causal_lo[None, :])
    return lob, jnp.maximum(hib, lob)


def _mask_bidx(mask_b, BH, heads, mask_mode):
    """Static mapper from the [B*H] grid index to the mask's batch dim.

    mask_mode disambiguates shapes (B == heads would otherwise be ambiguous):
    'one' [1,...], 'batch' [B,...] broadcast over heads, 'head' [H,...]
    broadcast over batch, 'bh' [B*H,...]."""
    if mask_mode == "one" or mask_b == 1:
        return lambda b: 0
    if mask_mode == "bh":
        return lambda b: b
    if mask_mode == "head":
        return lambda b: b % heads
    return lambda b: b // heads  # 'batch'


# ---------------------------------------------------------------------------
# jnp mirrors (exact kernel math, unblocked; the block loop is associative)
# ---------------------------------------------------------------------------

def _mirror_logits(q, k, causal, sm_scale, qseg, kseg, mask, heads,
                   mask_mode):
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32) * sm_scale,
                   k.astype(jnp.float32))
    if mask is not None:
        mb = _mask_bidx(mask.shape[0], BH, heads, mask_mode)
        idx = jnp.array([mb(b) for b in range(BH)])
        s = s + mask[idx].astype(jnp.float32)
    if causal:
        q_ids = jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 0)
        k_ids = jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 1)
        s = jnp.where(q_ids >= k_ids, s, NEG_INF)
    if qseg is not None:
        rep = BH // qseg.shape[0]
        qs = jnp.repeat(qseg, rep, axis=0)
        ks = jnp.repeat(kseg, rep, axis=0)
        s = jnp.where(qs[:, :, None] == ks[:, None, :], s, NEG_INF)
    return s


def _mirror_dropmask(seed, BH, Sq, Sk, dropout_p):
    """Mirror dropout uses jax.random (bit pattern differs from the TPU
    kernel's PRNG — like the reference's GPU-vs-CPU generators — but fwd/bwd
    agree because both derive from the same seed)."""
    key = jax.random.fold_in(jax.random.PRNGKey(0), seed[0])
    keep = jax.random.bernoulli(key, 1.0 - dropout_p, (BH, Sq, Sk))
    return keep.astype(jnp.float32) / (1.0 - dropout_p)


def _mirror_fwd(q, k, v, qseg, kseg, mask, seed, causal, sm_scale,
                dropout_p, heads, mask_mode="batch"):
    s = _mirror_logits(q, k, causal, sm_scale, qseg, kseg, mask, heads,
                       mask_mode)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    l_safe = jnp.maximum(l, 1e-30)
    pn = p / l_safe
    if dropout_p > 0:
        pn = pn * _mirror_dropmask(seed, *s.shape, dropout_p)
    out = jnp.einsum("bqk,bkd->bqd", pn, v.astype(jnp.float32)).astype(q.dtype)
    lse = m + jnp.log(l_safe)
    return out, lse


def _mirror_bwd(q, k, v, g, glse, lse, delta, qseg, kseg, mask, seed,
                causal, sm_scale, dropout_p, heads, mask_mode="batch"):
    s = _mirror_logits(q, k, causal, sm_scale, qseg, kseg, mask, heads,
                       mask_mode)
    p = jnp.exp(s - lse)
    gf = g.astype(jnp.float32)
    dp = jnp.einsum("bqd,bkd->bqk", gf, v.astype(jnp.float32))
    if dropout_p > 0:
        dmask = _mirror_dropmask(seed, *s.shape, dropout_p)
        dv = jnp.einsum("bqk,bqd->bkd", p * dmask, gf)
        dp = dp * dmask
    else:
        dv = jnp.einsum("bqk,bqd->bkd", p, gf)
    ds = p * (dp - delta + glse)
    dq = sm_scale * jnp.einsum("bqk,bkd->bqd", ds, k.astype(jnp.float32))
    dk = sm_scale * jnp.einsum("bqk,bqd->bkd", ds, q.astype(jnp.float32))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# custom_vjp core over (out, lse)
# ---------------------------------------------------------------------------

def _build_specs(BH, Sq, Sk, D, bq, bk, heads, qseg, kseg, mask,
                 seed, *, qseg_blocked, kseg_blocked, mask_mode="batch"):
    """in_specs/extra-args for the optional seg/mask/seed inputs, in the
    order _unpack expects them (after the dense tensor refs)."""
    specs, args = [], []
    if qseg is not None:
        Bseg = qseg.shape[0]
        bmap = (lambda b, i: (b // heads, 0)) if Bseg > 1 else (lambda b, i: (0, 0))
        if qseg_blocked:
            specs.append(pl.BlockSpec(
                (1, bq), (lambda b, i: ((b // heads) if Bseg > 1 else 0, i)),
                memory_space=pltpu.VMEM))
        else:
            specs.append(pl.BlockSpec((1, Sq), bmap, memory_space=pltpu.VMEM))
        if kseg_blocked:
            specs.append(pl.BlockSpec(
                (1, bk), (lambda b, i: ((b // heads) if Bseg > 1 else 0, i)),
                memory_space=pltpu.VMEM))
        else:
            specs.append(pl.BlockSpec((1, Sk), bmap, memory_space=pltpu.VMEM))
        args += [qseg, kseg]
        # lo/hi bound tables live in SMEM whole (tiny int32 tables)
        specs += [pl.BlockSpec(memory_space=pltpu.SMEM)] * 2
    if mask is not None:
        mb = mask.shape[0]
        mrows = mask.shape[1]
        if qseg_blocked:  # fwd/dq kernels: mask blocked along q, whole k
            specs.append(pl.BlockSpec(
                (1, mrows if mrows == 1 else bq, Sk),
                (lambda b, i, _mb=_mask_bidx(mb, BH, heads, mask_mode):
                 (_mb(b), 0 if mrows == 1 else i, 0)),
                memory_space=pltpu.VMEM))
        else:  # dkv kernel: whole q rows, blocked along k
            specs.append(pl.BlockSpec(
                (1, mrows, bk),
                (lambda b, i, _mb=_mask_bidx(mb, BH, heads, mask_mode):
                 (_mb(b), 0, i)),
                memory_space=pltpu.VMEM))
        args.append(mask)
    if seed is not None:
        specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(seed)
    return specs, args


def _core_fwd(q, k, v, qseg, kseg, mask, seed, causal, sm_scale,
              dropout_p, heads, mask_mode="batch"):
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    has_seg = qseg is not None
    has_mask = mask is not None
    mask_bytes = (0 if mask is None else mask.dtype.itemsize)
    fit = _fit_blocks(Sq, Sk, D, q.dtype.itemsize, mask_bytes, has_seg)
    vma = _out_vma(q, k, v, mask)
    if fit is None or _use_jnp_mirror(vma, dropout_p, *(fit or (1, 1))):
        if fit is None:
            _warn_fallback(Sq, Sk, D, has_mask)
        return _mirror_fwd(q, k, v, qseg, kseg, mask, seed, causal, sm_scale,
                           dropout_p, heads, mask_mode), True
    bq, bk = fit
    if has_seg:
        lob, hib = _varlen_bounds_q(qseg, kseg, bq, bk, causal)
    grid = (BH, Sq // bq)
    interpret = _interpret_mode()
    mrows = 0 if mask is None else mask.shape[1]

    extra_specs, extra_args = _build_specs(
        BH, Sq, Sk, D, bq, bk, heads, qseg, kseg, mask, seed,
        qseg_blocked=True, kseg_blocked=False, mask_mode=mask_mode)
    if has_seg:
        extra_args = extra_args[:2] + [lob, hib] + extra_args[2:]

    kern = functools.partial(
        _fwd_kernel, block_k=bk, sm_scale=sm_scale, causal=causal, seq_k=Sk,
        heads=heads, has_seg=has_seg, has_mask=has_mask, mask_rows=mrows,
        dropout_p=dropout_p)
    # x64 weak-type promotion inside kernels trips a Mosaic lowering
    # recursion; kernels are pure f32/bf16 so trace them with x64 off
    with x64_off():
        out, lse = pl.pallas_call(
            kern,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, Sk, D), lambda b, i: (b, 0, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, Sk, D), lambda b, i: (b, 0, 0), memory_space=pltpu.VMEM),
            ] + extra_specs,
            out_specs=[
                pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bq, 1), lambda b, i: (b, i, 0), memory_space=pltpu.VMEM),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((BH, Sq, D), q.dtype, vma=vma),
                jax.ShapeDtypeStruct((BH, Sq, 1), jnp.float32, vma=vma),
            ],
            interpret=interpret,
            name="flash_attention_fwd",
        )(q, k, v, *extra_args)
    return (out, lse), False


_warned = set()


def _warn_fallback(Sq, Sk, D, has_mask):
    key = (Sq, Sk, D, has_mask)
    if key not in _warned:
        _warned.add(key)
        warnings.warn(
            f"flash attention: Sq={Sq} Sk={Sk} D={D} mask={has_mask} exceeds "
            f"the VMEM blocking budget; running the XLA composition instead "
            f"(O(S^2) scores materialized).", stacklevel=3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11))
def _flash_core(q, k, v, qseg, kseg, mask, seed, causal, sm_scale,
                dropout_p, heads, mask_mode="batch"):
    (out, lse), _ = _core_fwd(q, k, v, qseg, kseg, mask, seed, causal,
                              sm_scale, dropout_p, heads, mask_mode)
    return out, lse


def _flash_core_fwd(q, k, v, qseg, kseg, mask, seed, causal, sm_scale,
                    dropout_p, heads, mask_mode="batch"):
    (out, lse), _ = _core_fwd(q, k, v, qseg, kseg, mask, seed, causal,
                              sm_scale, dropout_p, heads, mask_mode)
    return (out, lse), (q, k, v, qseg, kseg, mask, seed, out, lse)


def _flash_core_bwd(causal, sm_scale, dropout_p, heads, mask_mode, res, cot):
    q, k, v, qseg, kseg, mask, seed, out, lse = res
    g, glse = cot
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    has_seg = qseg is not None
    has_mask = mask is not None
    mask_bytes = (0 if mask is None else mask.dtype.itemsize)
    fit = _fit_blocks(Sq, Sk, D, q.dtype.itemsize, mask_bytes, has_seg)
    vma = _out_vma(q, k, v, mask, g)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)  # [BH, Sq, 1]
    glse = (jnp.zeros_like(delta) if glse is None
            else glse.astype(jnp.float32).reshape(BH, Sq, 1))

    def _int_cots():
        cots = []
        for a in (qseg, kseg):
            cots.append(None if a is None
                        else np.zeros(a.shape, jax.dtypes.float0))
        cots.append(None if mask is None else jnp.zeros_like(mask))
        cots.append(None if seed is None
                    else np.zeros(seed.shape, jax.dtypes.float0))
        return tuple(cots)

    if fit is None or _use_jnp_mirror(vma, dropout_p, *(fit or (1, 1))):
        dq, dk, dv = _mirror_bwd(q, k, v, g, glse, lse, delta, qseg, kseg,
                                 mask, seed, causal, sm_scale, dropout_p,
                                 heads, mask_mode)
        return (dq, dk, dv) + _int_cots()

    bq, bk = fit
    interpret = _interpret_mode()
    mrows = 0 if mask is None else mask.shape[1]
    if has_seg:
        lob_q, hib_q = _varlen_bounds_q(qseg, kseg, bq, bk, causal)
        lob_k, hib_k = _varlen_bounds_kv(qseg, kseg, bq, bk, causal)

    dq_specs, dq_args = _build_specs(
        BH, Sq, Sk, D, bq, bk, heads, qseg, kseg, mask, seed,
        qseg_blocked=True, kseg_blocked=False, mask_mode=mask_mode)
    if has_seg:
        dq_args = dq_args[:2] + [lob_q, hib_q] + dq_args[2:]
    dkv_specs, dkv_args = _build_specs(
        BH, Sq, Sk, D, bq, bk, heads, qseg, kseg, mask, seed,
        qseg_blocked=False, kseg_blocked=True, mask_mode=mask_mode)
    if has_seg:
        dkv_args = dkv_args[:2] + [lob_k, hib_k] + dkv_args[2:]

    with x64_off():
        dq = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, block_k=bk, sm_scale=sm_scale,
                              causal=causal, seq_k=Sk, heads=heads,
                              has_seg=has_seg, has_mask=has_mask,
                              mask_rows=mrows, dropout_p=dropout_p),
            grid=(BH, Sq // bq),
            in_specs=[
                pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, Sk, D), lambda b, i: (b, 0, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, Sk, D), lambda b, i: (b, 0, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bq, 1), lambda b, i: (b, i, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bq, 1), lambda b, i: (b, i, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bq, 1), lambda b, i: (b, i, 0), memory_space=pltpu.VMEM),
            ] + dq_specs,
            out_specs=pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q.dtype, vma=vma),
            interpret=interpret,
            name="flash_attention_bwd_dq",
        )(q, k, v, g, lse, delta, glse, *dq_args)

        dk, dv = pl.pallas_call(
            functools.partial(_bwd_dkv_kernel, block_q=bq, sm_scale=sm_scale,
                              causal=causal, seq_q=Sq, heads=heads,
                              has_seg=has_seg, has_mask=has_mask,
                              mask_rows=mrows, dropout_p=dropout_p),
            grid=(BH, Sk // bk),
            in_specs=[
                pl.BlockSpec((1, Sq, D), lambda b, i: (b, 0, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bk, D), lambda b, i: (b, i, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bk, D), lambda b, i: (b, i, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, Sq, D), lambda b, i: (b, 0, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, Sq, 1), lambda b, i: (b, 0, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, Sq, 1), lambda b, i: (b, 0, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, Sq, 1), lambda b, i: (b, 0, 0), memory_space=pltpu.VMEM),
            ] + dkv_specs,
            out_specs=[
                pl.BlockSpec((1, bk, D), lambda b, i: (b, i, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bk, D), lambda b, i: (b, i, 0), memory_space=pltpu.VMEM),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((BH, Sk, D), k.dtype, vma=vma),
                jax.ShapeDtypeStruct((BH, Sk, D), v.dtype, vma=vma),
            ],
            interpret=interpret,
            name="flash_attention_bwd_dkv",
        )(q, k, v, g, lse, delta, glse, *dkv_args)
    return (dq, dk, dv) + _int_cots()


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


# ---------------------------------------------------------------------------
# back-compat internal API (used by tests and the pp pipeline)
# ---------------------------------------------------------------------------

def _flash_bhsd(q, k, v, causal, sm_scale):
    out, _ = _flash_core(q, k, v, None, None, None, None, causal, sm_scale,
                         0.0, 1)
    return out


def _flash_fwd(q, k, v, causal, sm_scale):
    (out, lse), _ = _core_fwd(q, k, v, None, None, None, None, causal,
                              sm_scale, 0.0, 1)
    return out, lse


def _fwd_mirror(q, k, v, causal, sm_scale):
    return _mirror_fwd(q, k, v, None, None, None, None, causal, sm_scale,
                       0.0, 1)


def _bwd_mirror(q, k, v, g, lse, delta, causal, sm_scale):
    return _mirror_bwd(q, k, v, g, jnp.zeros_like(delta), lse, delta,
                       None, None, None, None, causal, sm_scale, 0.0, 1)


# ---------------------------------------------------------------------------
# public wrappers
# ---------------------------------------------------------------------------

def _canon_mask(attn_mask, B, Hq, Sq, Sk):
    """Normalize an attention mask broadcastable to [B, H, Sq, Sk] into the
    kernel's [N, 1|Sq, Sk] additive layout plus its broadcast mode ('one' /
    'batch' / 'head' / 'bh' — see _mask_bidx), WITHOUT materializing pure
    broadcast dims. Bool masks (True = keep) become 0/-1e30 bf16 (exactly
    representable); float masks stay f32."""
    m = attn_mask
    while m.ndim < 4:
        m = m[None]
    mb, mh, mq, mk = m.shape
    if mb not in (1, B) or mh not in (1, Hq) or mq not in (1, Sq) or mk not in (1, Sk):
        raise ValueError(
            f"attn_mask shape {attn_mask.shape} not broadcastable to "
            f"[{B}, {Hq}, {Sq}, {Sk}]")
    if m.dtype == jnp.bool_:
        m = jnp.where(m, 0.0, NEG_INF).astype(jnp.bfloat16)
    else:
        m = m.astype(jnp.float32)
    if mk == 1:
        m = jnp.broadcast_to(m, (mb, mh, mq, Sk))
    if mh == 1 and mb == 1:
        return m.reshape(1, mq, Sk), "one"
    if mh == 1:
        return m.reshape(mb, mq, Sk), "batch"   # broadcast over heads
    if mb == 1:
        return m.reshape(mh, mq, Sk), "head"    # broadcast over batch
    return m.reshape(mb * mh, mq, Sk), "bh"


def _dropout_seed(fixed_seed=None):
    if fixed_seed is not None:
        return jnp.asarray([fixed_seed], jnp.int32).reshape(1)
    from ..framework.random import next_key

    bits = jax.random.randint(next_key(), (1,), 0, np.int32(2 ** 31 - 1),
                              dtype=jnp.int32)
    return bits


def flash_attention_pallas(q, k, v, attn_mask=None, dropout_p=0.0,
                           is_causal=False, scale=None, training=True,
                           fixed_seed=None):
    """Drop-in for sdpa_ref: [B, S, H, D] layout, GQA via KV-head repeat.
    Masks stream through the kernel in blocks; dropout runs in-kernel with
    a counter-based PRNG (parity: the reference's flash_attn kernel applies
    dropout inside the fused kernel the same way)."""
    B, Sq, Hq, D = q.shape
    Sk, Hk = k.shape[1], k.shape[2]
    # trace-time only: one record per Pallas kernel build (CompileWatcher)
    from ..telemetry import perf as _perf

    _perf.compile_watcher().record_call(
        "pallas.flash_attention",
        _perf.abstract_signature((q, k, v), ("q", "k", "v")))
    if Hk != Hq:
        rep = Hq // Hk
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    sm_scale = scale if scale is not None else 1.0 / math.sqrt(D)
    if not training:
        dropout_p = 0.0
    mask = None
    mask_mode = "batch"
    if attn_mask is not None:
        if attn_mask.dtype != jnp.bool_:
            # Float (additive-bias) masks differentiate through the bias; the
            # kernel treats masks as constants (zero cotangent), so route the
            # bias case to the einsum composition like the reference does
            # (flash_attn accepts no bias there either — _math_attention runs).
            from ..nn.functional.attention import sdpa_ref

            key = (Sq, Sk, "float-bias")
            if key not in _warned:
                _warned.add(key)
                warnings.warn(
                    "flash attention: float additive bias routes to the "
                    "O(S^2) einsum composition so the bias differentiates; "
                    "use a bool mask to stay on the Pallas kernel.",
                    stacklevel=2)
            return sdpa_ref(q, k, v, attn_mask=attn_mask, dropout_p=dropout_p,
                            is_causal=is_causal, scale=scale,
                            training=training, fixed_seed=fixed_seed)
        mask, mask_mode = _canon_mask(attn_mask, B, Hq, Sq, Sk)
    seed = _dropout_seed(fixed_seed) if dropout_p > 0 else None

    def attend(q, k, v, mask, seed):
        # [B, S, H, D] -> [B*H, S, D] (B, H local to the shard under a mesh)
        b, h = q.shape[0], q.shape[2]

        def to_bhsd(x):
            return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], D)

        out, _ = _flash_core(to_bhsd(q), to_bhsd(k), to_bhsd(v), None, None,
                             mask, seed, is_causal, sm_scale,
                             # lint: allow-host-sync(dropout_p is a Python scalar at trace time)
                             float(dropout_p),
                             h, mask_mode)
        return out.reshape(b, h, Sq, D).transpose(0, 2, 1, 3)

    specs = _mesh_shard_specs(B, Hq, mask_mode if mask is not None else None)
    if specs is None:
        return attend(q, k, v, mask, seed)
    mesh, qkv_spec, axes = specs

    def attend_shard(q, k, v, mask, seed):
        if seed is not None and axes:  # decorrelate the shards' dropout
            seed = seed + jax.lax.axis_index(axes).astype(jnp.int32)
        return attend(q, k, v, mask, seed)

    rep = jax.sharding.PartitionSpec()
    return jax.shard_map(
        attend_shard, mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, rep, rep),
        out_specs=qkv_spec)(q, k, v, mask, seed)


def _mesh_shard_specs(B, H, mask_mode):
    """How to run the Mosaic kernel under the installed hybrid mesh, or None
    to call it directly.

    GSPMD cannot partition a Mosaic custom call ("Mosaic kernels cannot be
    automatically partitioned"), so on a multi-device TPU mesh the kernel
    runs once per shard under ``shard_map``: batch over the data axes, heads
    over 'mp' (the layout ColumnParallelLinear already gives q/k/v).
    Interpret mode lowers to plain HLO that GSPMD partitions itself, and a
    caller already inside a manual region (pp pipeline, ring attention)
    keeps its own mapping. Returns ``(mesh, qkv_spec, sharded_axes)``."""
    if _interpret_mode() or jax.sharding.get_abstract_mesh().manual_axes:
        return None
    from ..distributed.mesh import current_mesh

    mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        return None
    if mask_mode not in (None, "one"):
        raise NotImplementedError(
            f"flash attention under a {mesh.size}-device mesh takes no mask "
            f"or one shared by every batch row and head; got a "
            f"'{mask_mode}'-mode mask")
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    data = tuple(a for a in ("dp", "sharding") if shape[a] > 1)
    if B % math.prod(shape[a] for a in data):
        data = ()  # batch not divisible: every data rank attends to all rows
    heads = ("mp",) if shape["mp"] > 1 else ()
    if H % shape["mp"]:
        raise ValueError(
            f"{H} attention heads do not divide over mp={shape['mp']}")
    spec = jax.sharding.PartitionSpec(data or None, None, heads or None, None)
    return mesh, spec, data + heads


def _segments_from_cu(cu, total, pad_to, pad_id):
    """Segment ids [1, pad_to] from cumulative lengths; tokens past cu[-1]
    and padding get `pad_id` (sorted, never matching a real segment)."""
    pos = jnp.arange(pad_to, dtype=jnp.int32)
    seg = jnp.searchsorted(cu.astype(jnp.int32), pos, side="right") - 1
    nseg = cu.shape[0] - 1
    valid = pos < jnp.minimum(jnp.int32(total), cu[-1])
    seg = jnp.where(valid & (seg < nseg), seg, pad_id)
    return seg[None, :]


def flash_attn_varlen_pallas(q, k, v, cu_seqlens_q, cu_seqlens_k,
                             max_seqlen_q=None, max_seqlen_k=None,
                             scale=None, dropout_p=0.0, causal=False,
                             training=True, fixed_seed=None):
    """Varlen (packed / unpadded) flash attention.

    q/k/v: [total_tokens, H, D]; cu_seqlens_*: int32 [num_seqs+1] cumulative
    offsets. Parity: flash_attn_unpadded
    (/root/reference/python/paddle/nn/functional/flash_attention.py:272).
    Sequences are packed contiguously; segment ids derived from cu_seqlens
    mask cross-sequence attention, and block-range tables skip non-adjacent
    sequences' blocks entirely. Causal masking is positional within the
    packed layout (valid when cu_seqlens_q == cu_seqlens_k, the reference's
    supported decode/training case)."""
    Tq, Hq, D = q.shape
    Tk, Hk = k.shape[0], k.shape[1]
    if Hk != Hq:
        rep = Hq // Hk
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    sm_scale = scale if scale is not None else 1.0 / math.sqrt(D)
    if not training:
        dropout_p = 0.0
    if causal and cu_seqlens_q.shape != cu_seqlens_k.shape:
        raise ValueError(
            "causal varlen attention requires cu_seqlens_q == cu_seqlens_k "
            "(positional causality is defined within aligned packed "
            "sequences); got different shapes")
    if causal and cu_seqlens_q is not cu_seqlens_k:
        import numpy as _np

        try:  # concrete inputs: validate values loudly
            if not bool(_np.array_equal(_np.asarray(cu_seqlens_q),
                                        _np.asarray(cu_seqlens_k))):
                raise ValueError(
                    "causal varlen attention requires cu_seqlens_q == "
                    "cu_seqlens_k; per-sequence q/k lengths differ")
        except jax.errors.TracerArrayConversionError:
            pass  # traced: documented precondition, cannot check at trace time
    nseg = cu_seqlens_q.shape[0] - 1

    def pad_to(n):
        return max(128, -(-n // 128) * 128)

    Pq, Pk = pad_to(Tq), pad_to(Tk)
    qseg = _segments_from_cu(cu_seqlens_q, Tq, Pq, nseg + 1)
    kseg = _segments_from_cu(cu_seqlens_k, Tk, Pk, nseg + 2)

    def to_hsd(x, P, T):
        x = jnp.pad(x, ((0, P - T), (0, 0), (0, 0)))
        return x.transpose(1, 0, 2)  # [H, P, D]

    seed = _dropout_seed(fixed_seed) if dropout_p > 0 else None
    out, _ = _flash_core(to_hsd(q, Pq, Tq), to_hsd(k, Pk, Tk),
                         to_hsd(v, Pk, Tk), qseg, kseg, None, seed,
                         causal, sm_scale,
                         # lint: allow-host-sync(dropout_p is a Python scalar at trace time)
                         float(dropout_p), Hq)
    return out.transpose(1, 0, 2)[:Tq]
