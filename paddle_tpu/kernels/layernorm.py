"""Fused LayerNorm Pallas kernel (the reference's hand-fused
layer_norm CUDA kernel role, paddle/phi/kernels/gpu/layer_norm_kernel.cu).

One VMEM pass per row-block computes mean/rstd and the normalized output;
the custom vjp fuses the standard backward reductions. XLA already fuses
the jnp composition well on TPU — this kernel exists for the kernel-policy
surface (select with ``PADDLE_TPU_USE_PALLAS=1`` / ``set_use_pallas(True)``
after measuring on your shapes; the policy default keeps whichever path the
platform favors) and as the template for out-of-tree kernels
(docs/CUSTOM_OPS.md).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode as _interpret_mode

__all__ = ["layer_norm_pallas"]

_BLOCK_ROWS = 8


def _i0():
    # index-map constants must be i32: under jax_enable_x64 a python literal
    # traces as i64 and Mosaic rejects the mixed (i32, i64) index tuple
    return jnp.int32(0)


def _fwd_kernel(x_ref, w_ref, b_ref, o_ref, mean_ref, rstd_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)  # [rows, features]
    mean = jnp.mean(x, axis=1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xn = (x - mean) * rstd
    o_ref[...] = (xn * w_ref[...].astype(jnp.float32)
                  + b_ref[...].astype(jnp.float32)).astype(o_ref.dtype)
    # [rows, 1] layout: Mosaic rank-1 blocks must tile by 128, rank-2 with a
    # size-1 lane dim is exact
    mean_ref[...] = mean
    rstd_ref[...] = rstd


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def layer_norm_pallas(x, weight, bias, eps=1e-5):
    out, _, _ = _fwd(x, weight, bias, eps)
    return out


def _shapes(x):
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    return rows, x.shape[-1]


def _fwd(x, weight, bias, eps):
    rows, n = _shapes(x)
    # match the jnp composition's promotion (xn * w + b), so toggling the
    # kernel policy never changes downstream dtypes
    out_dtype = jnp.promote_types(jnp.promote_types(x.dtype, weight.dtype),
                                  bias.dtype)
    x2 = x.reshape(rows, n)
    grid = (pl.cdiv(rows, _BLOCK_ROWS),)
    out, mean, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((_BLOCK_ROWS, n), lambda i: (i, _i0()), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n), lambda i: (_i0(), _i0()), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n), lambda i: (_i0(), _i0()), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((_BLOCK_ROWS, n), lambda i: (i, _i0()), memory_space=pltpu.VMEM),
            pl.BlockSpec((_BLOCK_ROWS, 1), lambda i: (i, _i0()), memory_space=pltpu.VMEM),
            pl.BlockSpec((_BLOCK_ROWS, 1), lambda i: (i, _i0()), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, n), out_dtype),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ],
        interpret=_interpret_mode(),
    )(x2, weight.reshape(1, n), bias.reshape(1, n))
    return out.reshape(x.shape), mean, rstd


def _fwd_vjp(x, weight, bias, eps):
    out, mean, rstd = _fwd(x, weight, bias, eps)
    return out, (x, weight, bias, mean, rstd)


def _bwd_vjp(eps, res, g):
    """Backward as the jnp composition reusing the kernel's saved mean/rstd.

    A Pallas backward would accumulate dw/db into one [1, n] output block
    and so serialize its grid; whether the forward kernel beats XLA's
    fusion is not measured on this code. Composition kept: Pallas fwd +
    XLA bwd.
    """
    x, weight, bias, mean, rstd = res
    rows, n = _shapes(x)
    x2 = x.reshape(rows, n).astype(jnp.float32)
    g2 = g.reshape(rows, n).astype(jnp.float32)
    w = weight.astype(jnp.float32)[None, :]
    xn = (x2 - mean) * rstd
    gw = g2 * w
    m1 = jnp.mean(gw, axis=1, keepdims=True)
    m2 = jnp.mean(gw * xn, axis=1, keepdims=True)
    dx = (rstd * (gw - m1 - xn * m2)).astype(x.dtype).reshape(x.shape)
    dw = jnp.sum(g2 * xn, axis=0).astype(weight.dtype)
    db = jnp.sum(g2, axis=0).astype(bias.dtype)
    return dx, dw, db


layer_norm_pallas.defvjp(_fwd_vjp, _bwd_vjp)


# register in the op table so the custom-op variant surface sees it
from ..ops.registry import register_variant  # noqa: E402

register_variant("layer_norm", "pallas")(layer_norm_pallas)
