"""Fused Pallas CTC loss (warpctc parity — the reference vendors
third_party/warpctc and registers warpctc_kernel.cu; this is the TPU
lattice kernel, SURVEY §7 M5).

The lax.scan lattice in nn/functional/loss.py is correct but materializes
T sequential [B, S] HLO ops. Here the whole alpha (forward) / beta
(backward) recursion runs over VMEM-resident state in one kernel launch per
direction. The class-scatter of the gradient (ext-state posteriors ->
vocabulary) stays outside as a one-hot einsum: a dense [S, C] contraction
the MXU eats directly.

Layout (Mosaic):
- lattice state is [8, Sp]: batch rows on SUBLANES, extended-label states on
  LANES (Sp = S padded to 128) — each vector op advances 8 batch rows;
- grid tiles the batch in groups of 8; padded rows/states carry -1e30
  log-prob so shifted contributions vanish;
- lane shifts use pltpu.roll + iota masks;
- ragged input lengths are handled branch-free: the beta recursion runs the
  full static T and merges the per-row terminal initialization with a
  ``t == in_len-1`` mask (no dynamic trip counts);
- x64 traps: index-map constants, loop bounds and float literals must be
  explicit i32/f32 or Mosaic sees i64/f64 and refuses to lower.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode as _interpret_mode
from ._lattice import (BT as _BT, NEG as _NEG, i0 as _i0,
                       lanes as _lanes, neg32 as _neg32,
                       shift_left as _shift_left_f,
                       shift_right as _shift_right_f)

__all__ = ["ctc_loss_pallas"]







def _lse3(a, b, c):
    m = jnp.maximum(a, jnp.maximum(b, c))
    safe_m = jnp.where(m <= _neg32() / 2, jnp.float32(0.0), m)
    out = safe_m + jnp.log(
        jnp.exp(a - safe_m) + jnp.exp(b - safe_m) + jnp.exp(c - safe_m))
    return jnp.where(m <= _neg32() / 2, _neg32(), out)


_shift_right = _shift_right_f
_shift_left = _shift_left_f


def _alpha_kernel(logp_ref, same_ref, alpha_ref, carry_ref, *, Tt):
    """One TIME TILE of the forward recursion. logp_ref: [Tt, 8, Sp];
    alpha_ref out: [Tt, 8, Sp]; carry_ref scratch [8, Sp] holds the last
    alpha row across sequential time-tile grid steps (grid dim 1)."""
    Sp = logp_ref.shape[-1]
    tt = pl.program_id(1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (_BT, Sp), 1)
    same = same_ref[...]

    @pl.when(tt == 0)
    def _init_carry():
        carry_ref[...] = jnp.full((_BT, Sp), _NEG, jnp.float32)

    base = tt * jnp.int32(Tt)

    def step(t, alpha):
        lp_t = logp_ref[pl.ds(t, 1), :, :].reshape(_BT, Sp)
        a2 = _shift_right(alpha, 1, lane)
        a3 = jnp.where(same > 0, _neg32(), _shift_right(alpha, 2, lane))
        rec = _lse3(alpha, a2, a3) + lp_t
        # global t == 0 takes the start distribution instead of recursing
        new = jnp.where(base + t == 0,
                        jnp.where(lane < 2, lp_t, _neg32()), rec)
        alpha_ref[pl.ds(t, 1), :, :] = new[None]
        return new

    final = jax.lax.fori_loop(jnp.int32(0), jnp.int32(Tt), step,
                              carry_ref[...])
    carry_ref[...] = final


def _beta_kernel(logp_ref, same_ref, inlen_ref, slast_ref, beta_ref,
                 carry_ref, *, Tt, n_tt):
    """One TIME TILE of the branch-free ragged beta recursion, tiles
    processed high-to-low (reversed index map). The carry is
    ``tmp = logp[t+1] + beta[t+1]`` — the only cross-tile state the
    recursion needs, which also removes the old lp_next reread. Per-row
    terminal init (t == in_len-1) still merges in by mask, so ragged
    lengths stay branch-free across tiles."""
    Sp = logp_ref.shape[-1]
    tt = pl.program_id(1)  # 0 = highest time tile (index map reverses)
    lane = jax.lax.broadcasted_iota(jnp.int32, (_BT, Sp), 1)
    same = same_ref[...]
    in_len = inlen_ref[...]  # [8, 1] i32
    s_last = slast_ref[...]
    same_l2 = _shift_left(same.astype(jnp.float32), 2, lane, Sp)

    init = jnp.where(
        (lane == s_last) | ((lane == s_last - 1) & (s_last > 0)),
        jnp.float32(0.0), _neg32())  # [8, Sp]

    @pl.when(tt == 0)
    def _init_carry():
        carry_ref[...] = jnp.full((_BT, Sp), _NEG, jnp.float32)

    base = (jnp.int32(n_tt) - 1 - tt) * jnp.int32(Tt)

    def step(i, tmp_next):
        t = jnp.int32(Tt - 1) - i
        b2 = _shift_left(tmp_next, 1, lane, Sp)
        b3 = jnp.where(same_l2 > 0, _neg32(),
                       _shift_left(tmp_next, 2, lane, Sp))
        rec = _lse3(tmp_next, b2, b3)
        # rows where t is the terminal step take the init; rows with
        # t >= in_len keep -inf (tmp_next is -inf so rec stays -inf)
        new = jnp.where(base + t == in_len - 1, init, rec)
        beta_ref[pl.ds(t, 1), :, :] = new[None]
        lp_t = logp_ref[pl.ds(t, 1), :, :].reshape(_BT, Sp)
        return lp_t + new

    final = jax.lax.fori_loop(jnp.int32(0), jnp.int32(Tt), step,
                              carry_ref[...])
    carry_ref[...] = final


def _time_tile(T, Sp, budget_bytes=6 * 1024 * 1024):
    """Time-tile size: the WHOLE sequence when it fits the VMEM budget
    (single tile — zero padding, zero tile overhead; measured 37% faster
    than blind fixed-size tiling at T=400), otherwise the evenest split
    into the fewest budget-fitting tiles (padding < one tile row count)."""
    per_row = 4 * _BT * Sp * 4  # in + out, double-buffered, f32
    max_rows = max(1, budget_bytes // per_row)
    if T <= max_rows:
        return T
    n_tiles = -(-T // max_rows)
    return -(-T // n_tiles)


def _prep(log_probs, labels, blank):
    """ext labels, gathered ext log-probs [Tp, B, Sp], same-mask [B, Sp] —
    batch padded to a multiple of 8 sublane rows, time padded to a multiple
    of the VMEM time-tile (padded steps carry -inf log-probs: the alpha
    recursion freewheels, the beta recursion keeps them at -inf)."""
    T, B, C = log_probs.shape
    L = labels.shape[1]
    S = 2 * L + 1
    Sp = _lanes(S)
    Bp = ((B + _BT - 1) // _BT) * _BT
    Tt = _time_tile(T, Sp)
    Tp = ((T + Tt - 1) // Tt) * Tt
    lbl = labels.astype(jnp.int32)
    ext = jnp.full((B, S), blank, jnp.int32)
    ext = ext.at[:, 1::2].set(lbl)
    logp_ext = jnp.take_along_axis(
        log_probs.astype(jnp.float32),
        jnp.broadcast_to(ext[None], (T, B, S)), axis=2)  # [T, B, S]
    same = jnp.concatenate(
        [jnp.ones((B, 2), jnp.int32),
         (ext[:, 2:] == ext[:, :-2]).astype(jnp.int32)], axis=1)
    logp_ext = jnp.pad(logp_ext, ((0, Tp - T), (0, Bp - B), (0, Sp - S)),
                       constant_values=_NEG)
    same = jnp.pad(same, ((0, Bp - B), (0, Sp - S)), constant_values=1)
    return ext, logp_ext, same, S, Sp, Bp, Tt


def _alphas(logp_ext, same, Tt, Sp):
    Tp, Bp = logp_ext.shape[0], logp_ext.shape[1]
    n_tt = Tp // Tt
    return pl.pallas_call(
        functools.partial(_alpha_kernel, Tt=Tt),
        grid=(Bp // _BT, n_tt),
        in_specs=[
            pl.BlockSpec((Tt, _BT, Sp), lambda b, tt: (tt, b, _i0())),
            pl.BlockSpec((_BT, Sp), lambda b, tt: (b, _i0())),
        ],
        out_specs=pl.BlockSpec((Tt, _BT, Sp), lambda b, tt: (tt, b, _i0())),
        out_shape=jax.ShapeDtypeStruct((Tp, Bp, Sp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((_BT, Sp), jnp.float32)],
        interpret=_interpret_mode(),
    )(logp_ext, same)


def _betas(logp_ext, same, in_len, s_last, Tt, Sp):
    Tp, Bp = logp_ext.shape[0], logp_ext.shape[1]
    n_tt = Tp // Tt
    B = in_len.shape[0]
    inlen2 = jnp.pad(in_len.astype(jnp.int32), (0, Bp - B),
                     constant_values=-1)[:, None]  # [Bp, 1]
    slast2 = jnp.pad(s_last.astype(jnp.int32), (0, Bp - B),
                     constant_values=-1)[:, None]
    rev = lambda b, tt: (jnp.int32(n_tt - 1) - tt, b, _i0())
    return pl.pallas_call(
        functools.partial(_beta_kernel, Tt=Tt, n_tt=n_tt),
        grid=(Bp // _BT, n_tt),
        in_specs=[
            pl.BlockSpec((Tt, _BT, Sp), rev),
            pl.BlockSpec((_BT, Sp), lambda b, tt: (b, _i0())),
            pl.BlockSpec((_BT, 1), lambda b, tt: (b, _i0())),
            pl.BlockSpec((_BT, 1), lambda b, tt: (b, _i0())),
        ],
        out_specs=pl.BlockSpec((Tt, _BT, Sp), rev),
        out_shape=jax.ShapeDtypeStruct((Tp, Bp, Sp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((_BT, Sp), jnp.float32)],
        interpret=_interpret_mode(),
    )(logp_ext, same, inlen2, slast2)


def _loglik(alphas, in_len, lbl_len, S):
    """Final log-likelihood from saved alphas [T, Bp, Sp]: states 2*L and
    2*L-1 at t = in_len-1."""
    B = in_len.shape[0]
    T = alphas.shape[0]
    t_idx = jnp.clip(in_len.astype(jnp.int32) - 1, 0, T - 1)
    last = alphas[t_idx, jnp.arange(B)]  # [B, Sp]
    s_last = 2 * lbl_len.astype(jnp.int32)
    a_end = jnp.take_along_axis(last, s_last[:, None], axis=1)[:, 0]
    a_pre = jnp.take_along_axis(
        last, jnp.clip(s_last - 1, 0, S - 1)[:, None], axis=1)[:, 0]
    # empty label (s_last == 0): only the all-blank state ends the path —
    # clipping s_last-1 to 0 would double-count it (a ln2 bias)
    a_pre = jnp.where(s_last > 0, a_pre, _NEG)
    return jnp.logaddexp(a_end, a_pre), s_last


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def ctc_loss_pallas(log_probs, labels, input_lengths, label_lengths,
                    blank=0):
    """Per-sample negative log-likelihood [B] (reduction applied by the
    caller, like phi::WarpctcKernel). Differentiable wrt log_probs."""
    loss, _ = _fwd(log_probs, labels, input_lengths, label_lengths, blank)
    return loss


def _fwd(log_probs, labels, input_lengths, label_lengths, blank):
    ext, logp_ext, same, S, Sp, Bp, Tt = _prep(log_probs, labels, blank)
    alphas = _alphas(logp_ext, same, Tt, Sp)
    ll, s_last = _loglik(alphas, input_lengths, label_lengths, S)
    # logp_ext is NOT saved: it is one cheap gather away from log_probs
    # (recomputed in _bwd) and would otherwise pin T*Bp*Sp floats in HBM
    # across forward->backward
    res = (log_probs, labels, input_lengths, label_lengths,
           alphas, ll, s_last)
    return -ll, res


def _bwd(blank, res, g):
    (log_probs, labels, in_len, lbl_len, alphas, ll, s_last) = res
    T, B, C = log_probs.shape
    ext, logp_ext, same, S, Sp, Bp, Tt = _prep(log_probs, labels, blank)
    betas = _betas(logp_ext, same, in_len, s_last, Tt, Sp)
    # posterior over ext states; rows t >= in_len carry -inf betas -> 0
    # (time-padded rows t >= T are sliced off)
    post = jnp.exp(alphas[:T, :B] + betas[:T, :B]
                   - ll[None, :, None])  # [T, B, Sp]
    g_ext = -post * g[None, :, None]  # d(-ll)/dlogp_ext * upstream
    # scatter ext states back to classes on the MXU: one-hot [B,S,C] einsum
    onehot = jax.nn.one_hot(ext, C, dtype=g_ext.dtype)  # [B, S, C]
    g_logp = jnp.einsum("tbs,bsc->tbc", g_ext[:, :, :S],
                        onehot).astype(log_probs.dtype)
    f0 = lambda x: np.zeros(x.shape, jax.dtypes.float0)
    return (g_logp, f0(labels), f0(in_len), f0(lbl_len))


def fits_vmem(T, L, budget_bytes=6 * 1024 * 1024):
    """Time-tiling (round 4) removed the old whole-T VMEM ceiling: any T
    works as long as a SINGLE time row's in+out blocks fit the budget
    (pathologically long label sequences are the only remaining fallback)."""
    Sp = _lanes(2 * L + 1)
    return 4 * _BT * Sp * 4 <= budget_bytes


ctc_loss_pallas.defvjp(_fwd, _bwd)
