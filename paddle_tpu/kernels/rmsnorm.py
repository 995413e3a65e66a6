"""Fused RMSNorm(+residual) Pallas kernel.

Candidate from the round-5 op-bench loop (VERDICT r4 next #5): the Llama
block applies ``h = x + attn_out`` followed by RMSNorm — bandwidth-bound
elementwise work. This kernel fuses the residual add, the rms reduction,
and the normalize/scale into ONE VMEM pass per row block, with a fused
backward (dx + per-block dw partials).

Opt-in (``kernels.rmsnorm_impl``) and never timed on the chip: whether it
beats XLA's fusion there is not known. One run of ``mistral7b-train-2k``
decides between default and deletion (ROADMAP Design 6).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode as _interpret_mode, x64_off

__all__ = ["rmsnorm_residual_pallas", "rmsnorm_pallas"]

_BLOCK_ROWS = 256


def _vma(*xs):
    out = frozenset()
    for x in xs:
        out |= jax.typeof(x).vma
    return out


def _fwd_kernel(*refs, eps, has_resid):
    if has_resid:
        x_ref, r_ref, w_ref, o_ref, rms_ref = refs
        x = x_ref[...].astype(jnp.float32) + r_ref[...].astype(jnp.float32)
    else:
        x_ref, w_ref, o_ref, rms_ref = refs
        x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(jnp.square(x), axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(ms + eps)
    rms_ref[...] = rstd
    o_ref[...] = (x * rstd * w_ref[...].astype(jnp.float32)).astype(
        o_ref.dtype)


def _bwd_kernel(*refs, eps, has_resid):
    if has_resid:
        x_ref, r_ref, w_ref, rms_ref, g_ref, dx_ref, dwp_ref = refs
        x = x_ref[...].astype(jnp.float32) + r_ref[...].astype(jnp.float32)
    else:
        x_ref, w_ref, rms_ref, g_ref, dx_ref, dwp_ref = refs
        x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    rstd = rms_ref[...]
    gw = g * w
    # d/dx of x*rstd(x)*w: rstd*gw - x * rstd^3 * mean(x*gw)
    dot = jnp.mean(x * gw, axis=1, keepdims=True)
    dx = rstd * gw - x * (rstd ** 3) * dot
    dx_ref[...] = dx.astype(dx_ref.dtype)
    # Mosaic needs >=8 sublanes per block: row 0 carries the partial,
    # rows 1-7 are zero (summed away host-side)
    part = jnp.sum((x * rstd) * g, axis=0, keepdims=True)
    dwp_ref[...] = jnp.concatenate(
        [part, jnp.zeros((7, part.shape[1]), jnp.float32)], axis=0)


def _rows_block(n_rows):
    b = min(_BLOCK_ROWS, n_rows)
    while n_rows % b:
        b //= 2
    return max(b, 1)


def _row_spec(br, F):
    return pl.BlockSpec((br, F), lambda i: (i, 0), memory_space=pltpu.VMEM)


def _w_spec(F):
    return pl.BlockSpec((1, F), lambda i: (0, 0), memory_space=pltpu.VMEM)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _rmsnorm_core(x, resid, w, eps, has_resid):
    out, _ = _fwd(x, resid, w, eps, has_resid)
    return out


def _mirror(x, resid, w, eps, has_resid):
    """jnp transcription for interpret-under-shard_map (check_vma): the
    Pallas HLO interpreter cannot trace there, same policy as
    flash_attention's mirrors."""
    v = x.astype(jnp.float32)
    if has_resid:
        v = v + resid.astype(jnp.float32)
    rstd = jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + eps)
    return (v * rstd * w.astype(jnp.float32)).astype(x.dtype), rstd


def _fwd(x, resid, w, eps, has_resid):
    R, F = x.shape
    br = _rows_block(R)
    interp = _interpret_mode()
    vma = _vma(x, resid, w)
    if interp and vma:
        return _mirror(x, resid, w, eps, has_resid)
    args = (x, resid, w.reshape(1, F)) if has_resid else (x, w.reshape(1, F))
    in_specs = ([_row_spec(br, F)] * (2 if has_resid else 1)) + [_w_spec(F)]
    # x64 weak-type promotion inside kernels trips Mosaic (mixed i32/i64
    # index tuples); kernels are pure f32/bf16 so trace with x64 off
    with x64_off():
            out, rstd = pl.pallas_call(
            functools.partial(_fwd_kernel, eps=eps, has_resid=has_resid),
            grid=(R // br,),
            in_specs=in_specs,
            out_specs=[_row_spec(br, F),
                       pl.BlockSpec((br, 1), lambda i: (i, 0),
                                    memory_space=pltpu.VMEM)],
            out_shape=[jax.ShapeDtypeStruct((R, F), x.dtype, vma=vma),
                       jax.ShapeDtypeStruct((R, 1), jnp.float32, vma=vma)],
            interpret=interp,
        )(*args)
    return out, rstd


def _core_fwd(x, resid, w, eps, has_resid):
    out, rstd = _fwd(x, resid, w, eps, has_resid)
    return out, (x, resid, w, rstd)


def _mirror_bwd(x, resid, w, rstd, g, has_resid):
    v = x.astype(jnp.float32)
    if has_resid:
        v = v + resid.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    gw = gf * w.astype(jnp.float32)
    dot = jnp.mean(v * gw, axis=1, keepdims=True)
    dx = rstd * gw - v * (rstd ** 3) * dot
    dw = jnp.sum((v * rstd) * gf, axis=0)
    return dx.astype(x.dtype), dw.astype(w.dtype)


def _core_bwd(eps, has_resid, res, g):
    x, resid, w, rstd = res
    R, F = x.shape
    br = _rows_block(R)
    interp = _interpret_mode()
    vma = _vma(x, resid, w, g)
    if interp and vma:
        dx, dw = _mirror_bwd(x, resid, w, rstd, g, has_resid)
        return dx, (dx.astype(resid.dtype) if has_resid
                    else jnp.zeros_like(resid)), dw
    args = ((x, resid, w.reshape(1, F), rstd, g) if has_resid
            else (x, w.reshape(1, F), rstd, g))
    in_specs = ([_row_spec(br, F)] * (2 if has_resid else 1)
                + [_w_spec(F),
                   pl.BlockSpec((br, 1), lambda i: (i, 0),
                                memory_space=pltpu.VMEM),
                   _row_spec(br, F)])
    with x64_off():
            dx, dw_part = pl.pallas_call(
            functools.partial(_bwd_kernel, eps=eps, has_resid=has_resid),
            grid=(R // br,),
            in_specs=in_specs,
            out_specs=[_row_spec(br, F),
                       pl.BlockSpec((8, F), lambda i: (i, 0),
                                    memory_space=pltpu.VMEM)],
            out_shape=[jax.ShapeDtypeStruct((R, F), x.dtype, vma=vma),
                       jax.ShapeDtypeStruct((8 * (R // br), F),
                                            jnp.float32, vma=vma)],
            interpret=interp,
        )(*args)
    dw = jnp.sum(dw_part, axis=0).astype(w.dtype)
    # residual-add backward: both addends receive dx
    return dx, (dx.astype(resid.dtype) if has_resid
                else jnp.zeros_like(resid)), dw


_rmsnorm_core.defvjp(_core_fwd, _core_bwd)


def rmsnorm_residual_pallas(x, resid, weight, eps=1e-6):
    """RMSNorm(x + resid) * weight, returning (normed, x + resid). The sum
    is recomputed as a plain add outside the kernel (XLA fuses it into a
    neighbor; the kernel avoids a second full read for the norm)."""
    shape = x.shape
    F = shape[-1]
    out = _rmsnorm_core(x.reshape(-1, F), resid.reshape(-1, F), weight,
                        eps, True)
    return out.reshape(shape), x + resid


def rmsnorm_pallas(x, weight, eps=1e-6):
    shape = x.shape
    F = shape[-1]
    x2 = x.reshape(-1, F)
    out = _rmsnorm_core(x2, x2, weight, eps, False)  # resid arg unread
    return out.reshape(shape)
