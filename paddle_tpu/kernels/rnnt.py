"""Fused Pallas RNN-Transducer loss (warprnnt parity — the reference
vendors third_party/warprnnt; SURVEY §7 calls the RNNT lattice the hardest
M5 kernel).

The scan implementation (nn/functional/loss.py rnnt_loss) nests a U-scan
inside a T-scan: O(T·U) sequential HLO steps, because
``alpha[t,u] = lse(alpha[t-1,u]+blank[t-1,u], alpha[t,u-1]+emit[t,u-1])``
has a true prefix dependence along u. The kernel removes it analytically:
with ``E[u] = sum_{k<u} emit[t,k]`` (exclusive prefix sum) and
``base[u] = alpha[t-1,u] + blank[t-1,u]``,

    alpha[t,u] = E[u] + logcumsumexp(base - E)[u]

— both prefix operations are ASSOCIATIVE, so each time row costs
O(log U) lane-doubling steps (shift + add / shift + logaddexp) instead of
U sequential ones. The backward runs the mirrored suffix recursion and
emits the blank/emit posteriors directly; scatter back to the vocabulary
rides jax's VJP of the gather that built the inputs.

Layout matches kernels/ctc.py: batch rows on sublanes ([8, Up] tiles,
u on lanes), grid over batch tiles, branch-free ragged handling via a
``t == t_len-1`` terminal-row merge.
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
                       shift_left as _shift_l, shift_right as _shift_r)

__all__ = ["rnnt_core_pallas", "fits_vmem"]







def _lse2(a, b):
    m = jnp.maximum(a, b)
    safe_m = jnp.where(m <= _neg32() / 2, jnp.float32(0.0), m)
    out = safe_m + jnp.log(jnp.exp(a - safe_m) + jnp.exp(b - safe_m))
    return jnp.where(m <= _neg32() / 2, _neg32(), out)





def _cumsum_excl(x, lane, Up):
    """Exclusive prefix sum along lanes by doubling (values may be -1e30
    sentinels; the result is clamped back to the sentinel floor)."""
    s = _shift_r(x, 1, lane, jnp.float32(0.0))  # exclusive: shift first
    k = 1
    while k < Up:
        s = s + _shift_r(s, k, lane, jnp.float32(0.0))
        k *= 2
    return jnp.maximum(s, _neg32())


def _logcumsumexp(x, lane, Up):
    """Inclusive log-cumsum-exp along lanes by doubling."""
    s = x
    k = 1
    while k < Up:
        s = _lse2(s, _shift_r(s, k, lane, _neg32()))
        k *= 2
    return s


def _logcumsumexp_rev(x, lane, Up):
    """Suffix (right-to-left) log-cumsum-exp along lanes."""
    s = x
    k = 1
    while k < Up:
        s = _lse2(s, _shift_l(s, k, lane, Up, _neg32()))
        k *= 2
    return s


def _row_alpha(base, emit_row, lane, Up):
    """One time row: alpha[u] = E[u] + LCE(base - E)[u] with guards for
    -inf sentinels (base - E would otherwise produce +inf garbage)."""
    E = _cumsum_excl(emit_row, lane, Up)
    bad = (E < _neg32() / 2) | (base < _neg32() / 2)
    d = jnp.where(bad, _neg32(), base - E)
    lce = _logcumsumexp(d, lane, Up)
    out = E + lce
    return jnp.maximum(out, _neg32())


def _alpha_kernel(blank_ref, emit_ref, alpha_ref, *, T):
    """blank_ref/emit_ref: [T, 8, Up]; alpha_ref out: [T, 8, Up]."""
    Up = blank_ref.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (_BT, Up), 1)

    # t = 0: only the emit chain exists -> base = [0, -inf, ...]
    base0 = jnp.where(lane < 1, jnp.float32(0.0), _neg32())
    emit0 = emit_ref[pl.ds(0, 1), :, :].reshape(_BT, Up)
    alpha = _row_alpha(base0, emit0, lane, Up)
    alpha_ref[pl.ds(0, 1), :, :] = alpha[None]

    def step(t, alpha):
        blank_prev = blank_ref[pl.ds(t - 1, 1), :, :].reshape(_BT, Up)
        emit_t = emit_ref[pl.ds(t, 1), :, :].reshape(_BT, Up)
        base = jnp.maximum(alpha + blank_prev, _neg32())
        new = _row_alpha(base, emit_t, lane, Up)
        alpha_ref[pl.ds(t, 1), :, :] = new[None]
        return new

    jax.lax.fori_loop(jnp.int32(1), jnp.int32(T), step, alpha)


def _beta_grad_kernel(blank_ref, emit_ref, alpha_ref, tlen_ref, ulen_ref,
                      ll_ref, gb_ref, ge_ref, *, T):
    """Suffix recursion + posteriors in one pass.

    bhat[t,u] = lse(blank[t,u] + bhat[t+1,u], emit[t,u] + bhat[t,u+1]) with
    the virtual terminal row bhat[t_len, u] = (u == u_len ? 0 : -inf),
    merged branch-free at t == t_len-1. Emitted directly:
      gb[t,u] = exp(alpha + blank + bhat[t+1,u] - ll)   (negated outside)
      ge[t,u] = exp(alpha + emit  + bhat[t,u+1] - ll)
    """
    Up = blank_ref.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (_BT, Up), 1)
    t_len = tlen_ref[...]  # [8, 1] i32
    u_len = ulen_ref[...]
    ll = ll_ref[...]       # [8, 1] f32
    terminal = jnp.where(lane == u_len, jnp.float32(0.0), _neg32())

    bhat_carry = jnp.full((_BT, Up), _NEG, jnp.float32)

    def step(i, carry):
        t = jnp.int32(T - 1) - i
        blank_t = blank_ref[pl.ds(t, 1), :, :].reshape(_BT, Up)
        emit_t = emit_ref[pl.ds(t, 1), :, :].reshape(_BT, Up)
        alpha_t = alpha_ref[pl.ds(t, 1), :, :].reshape(_BT, Up)
        # bhat[t+1] seen from row t; the virtual terminal row merges in
        bhat_next = jnp.where(t == t_len - 1, terminal, carry)

        # suffix scan: bhat[t,u] = -F[u] + LCErev(A + F)[u],
        # A[u] = blank[t,u] + bhat_next[u], F[u] = exclusive emit prefix
        F = _cumsum_excl(emit_t, lane, Up)
        A = jnp.maximum(blank_t + bhat_next, _neg32())
        bad = (F < _neg32() / 2) | (A < _neg32() / 2)
        s = jnp.where(bad, _neg32(), A + F)
        lce = _logcumsumexp_rev(s, lane, Up)
        bhat_t = jnp.maximum(jnp.where(F < _neg32() / 2, _neg32(), lce - F),
                             _neg32())

        gb = jnp.exp(jnp.clip(alpha_t + blank_t + bhat_next - ll,
                              _neg32(), jnp.float32(0.0)))
        bhat_right = _shift_l(bhat_t, 1, lane, Up, _neg32())
        ge = jnp.exp(jnp.clip(alpha_t + emit_t + bhat_right - ll,
                              _neg32(), jnp.float32(0.0)))
        # rows past the input length contribute nothing
        live = (t < t_len).astype(jnp.float32)
        gb_ref[pl.ds(t, 1), :, :] = (gb * live)[None]
        ge_ref[pl.ds(t, 1), :, :] = (ge * live)[None]
        return bhat_t

    jax.lax.fori_loop(jnp.int32(0), jnp.int32(T), step, bhat_carry)


def fits_vmem(T, U, budget_bytes=6 * 1024 * 1024):
    """Untiled [T, 8, Up] blocks: forward holds blank+emit+alpha (3),
    backward adds the two grad outputs."""
    Up = _lanes(U + 1)
    return 5 * (T * _BT * Up * 4) <= budget_bytes


def _pad_batch(x, Bp, fill):
    B = x.shape[1]
    return jnp.pad(x, ((0, 0), (0, Bp - B), (0, 0)), constant_values=fill)


def _specs(T, Up, n):
    return [pl.BlockSpec((T, _BT, Up), lambda b: (_i0(), b, _i0()))
            for _ in range(n)]


def _scalar_spec():
    return pl.BlockSpec((_BT, 1), lambda b: (b, _i0()))


@functools.partial(jax.custom_vjp, nondiff_argnums=())
def rnnt_core_pallas(blank_lp, emit_lp, t_lens, u_lens):
    """Per-sample negative log-likelihood [B].

    blank_lp: [T, B, Up] log P(blank at (t, u)) (u >= U1 lanes = -1e30);
    emit_lp: [T, B, Up] log P(emit label u at (t, u)) (u >= u_len = -1e30).
    Differentiable wrt both log-prob lattices; the caller's gather from the
    [B,T,U1,V] joint output carries the grads back to the vocabulary."""
    loss, _ = _fwd(blank_lp, emit_lp, t_lens, u_lens)
    return loss


def _run_alpha(blank_lp, emit_lp, T, Up):
    Bp = blank_lp.shape[1]
    return pl.pallas_call(
        functools.partial(_alpha_kernel, T=T),
        grid=(Bp // _BT,),
        in_specs=_specs(T, Up, 2),
        out_specs=_specs(T, Up, 1)[0],
        out_shape=jax.ShapeDtypeStruct((T, Bp, Up), jnp.float32),
        interpret=_interpret_mode(),
    )(blank_lp, emit_lp)


def _fwd(blank_lp, emit_lp, t_lens, u_lens):
    T, B, Up = blank_lp.shape
    Bp = ((B + _BT - 1) // _BT) * _BT
    blank_p = _pad_batch(blank_lp.astype(jnp.float32), Bp, _NEG)
    emit_p = _pad_batch(emit_lp.astype(jnp.float32), Bp, _NEG)
    alphas = _run_alpha(blank_p, emit_p, T, Up)

    t_idx = jnp.clip(t_lens.astype(jnp.int32) - 1, 0, T - 1)
    u_idx = u_lens.astype(jnp.int32)
    a_end = alphas[t_idx, jnp.arange(B), u_idx]
    final_blank = blank_lp[t_idx, jnp.arange(B), u_idx]
    ll = a_end + final_blank
    res = (blank_p, emit_p, alphas, t_lens, u_lens, ll, B)
    return -ll, res


def _bwd(res, g):
    blank_p, emit_p, alphas, t_lens, u_lens, ll, B = res
    T, Bp, Up = blank_p.shape
    tl = jnp.pad(t_lens.astype(jnp.int32), (0, Bp - B),
                 constant_values=-1)[:, None]
    ul = jnp.pad(u_lens.astype(jnp.int32), (0, Bp - B),
                 constant_values=-1)[:, None]
    llp = jnp.pad(ll.astype(jnp.float32), (0, Bp - B),
                  constant_values=0.0)[:, None]
    gb, ge = pl.pallas_call(
        functools.partial(_beta_grad_kernel, T=T),
        grid=(Bp // _BT,),
        in_specs=_specs(T, Up, 3) + [_scalar_spec(), _scalar_spec(),
                                     _scalar_spec()],
        out_specs=_specs(T, Up, 2),
        out_shape=[jax.ShapeDtypeStruct((T, Bp, Up), jnp.float32),
                   jax.ShapeDtypeStruct((T, Bp, Up), jnp.float32)],
        interpret=_interpret_mode(),
    )(blank_p, emit_p, alphas, tl, ul, llp)
    # loss = -ll: posteriors negate; upstream g broadcasts per sample
    gB = -gb[:, :B] * g[None, :, None]
    gE = -ge[:, :B] * g[None, :, None]
    f0 = lambda x: np.zeros(x.shape, jax.dtypes.float0)
    return (gB, gE, f0(t_lens), f0(u_lens))


rnnt_core_pallas.defvjp(_fwd, _bwd)
