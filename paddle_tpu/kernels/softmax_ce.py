"""Streaming softmax-cross-entropy Pallas kernel (32k-vocab LM head).

Candidate from the round-5 op-bench loop: XLA's log_softmax+gather keeps
[N, V] residuals alive for the backward; this kernel saves only the per-row
logsumexp ([N] floats) and recomputes the softmax block-wise in the fused
backward (softmax - onehot), the FlashAttention trick applied to the LM
loss. Opt-in (``kernels.softmax_ce_impl``) and never timed on the chip
(ROADMAP Design 6).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode as _interpret_mode, x64_off

__all__ = ["softmax_ce_pallas"]

_BLOCK_ROWS = 8


def _vma(*xs):
    out = frozenset()
    for x in xs:
        out |= jax.typeof(x).vma
    return out


def _fwd_kernel(x_ref, lab_ref, loss_ref, lse_ref):
    x = x_ref[...].astype(jnp.float32)        # [br, V]
    lab = lab_ref[...]                        # [br, 1] int32
    m = jnp.max(x, axis=1, keepdims=True)
    lse = m + jnp.log(jnp.sum(jnp.exp(x - m), axis=1, keepdims=True))
    v_ids = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    picked = jnp.sum(jnp.where(v_ids == lab, x, 0.0), axis=1, keepdims=True)
    loss_ref[...] = lse - picked
    lse_ref[...] = lse


def _bwd_kernel(x_ref, lab_ref, lse_ref, g_ref, dx_ref):
    x = x_ref[...].astype(jnp.float32)
    lab = lab_ref[...]
    lse = lse_ref[...]
    g = g_ref[...]                            # [br, 1]
    p = jnp.exp(x - lse)                      # softmax, recomputed
    v_ids = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    onehot = (v_ids == lab).astype(jnp.float32)
    dx_ref[...] = (g * (p - onehot)).astype(dx_ref.dtype)


def _rows_block(n):
    b = min(_BLOCK_ROWS, n)
    while n % b:
        b //= 2
    return max(b, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=())
def _ce_core(x, labels):
    loss, _ = _fwd(x, labels)
    return loss


def _mirror_fwd(x, labels):
    xf = x.astype(jnp.float32)
    m = jnp.max(xf, axis=1, keepdims=True)
    lse = m + jnp.log(jnp.sum(jnp.exp(xf - m), axis=1, keepdims=True))
    picked = jnp.take_along_axis(
        xf, labels.reshape(-1, 1).astype(jnp.int32), axis=1)
    return (lse - picked)[:, 0], lse


def _fwd(x, labels):
    N, V = x.shape
    br = _rows_block(N)
    interp = _interpret_mode()
    vma = _vma(x, labels)
    if interp and vma:
        return _mirror_fwd(x, labels)
    with x64_off():
            loss, lse = pl.pallas_call(
            _fwd_kernel,
            grid=(N // br,),
            in_specs=[
                pl.BlockSpec((br, V), lambda i: (i, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((br, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            ],
            out_specs=[
                pl.BlockSpec((br, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((br, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            ],
            out_shape=[jax.ShapeDtypeStruct((N, 1), jnp.float32, vma=vma),
                       jax.ShapeDtypeStruct((N, 1), jnp.float32, vma=vma)],
            interpret=interp,
        )(x, labels.reshape(N, 1).astype(jnp.int32))
    return loss[:, 0], lse


def _core_fwd(x, labels):
    loss, lse = _fwd(x, labels)
    return loss, (x, labels, lse)


def _core_bwd(res, g):
    x, labels, lse = res
    N, V = x.shape
    br = _rows_block(N)
    interp = _interpret_mode()
    vma = _vma(x, labels, g)
    if interp and vma:
        p = jnp.exp(x.astype(jnp.float32) - lse)
        onehot = jax.nn.one_hot(labels.reshape(-1), V, dtype=jnp.float32)
        dx = (g.reshape(-1, 1).astype(jnp.float32) * (p - onehot)).astype(
            x.dtype)
        return dx, np.zeros(labels.shape, jax.dtypes.float0)
    with x64_off():
            dx = pl.pallas_call(
            _bwd_kernel,
            grid=(N // br,),
            in_specs=[
                pl.BlockSpec((br, V), lambda i: (i, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((br, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((br, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((br, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((br, V), lambda i: (i, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((N, V), x.dtype, vma=vma),
            interpret=interp,
        )(x, labels.reshape(N, 1).astype(jnp.int32), lse,
          g.reshape(N, 1).astype(jnp.float32))
    return dx, np.zeros(labels.shape, jax.dtypes.float0)


_ce_core.defvjp(_core_fwd, _core_bwd)


def softmax_ce_pallas(logits, labels):
    """Per-example CE loss over the last axis; logits [..., V], int labels
    [...]. Returns loss [...] float32."""
    V = logits.shape[-1]
    lead = logits.shape[:-1]
    loss = _ce_core(logits.reshape(-1, V), labels.reshape(-1))
    return loss.reshape(lead)
