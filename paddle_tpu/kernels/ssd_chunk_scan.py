"""Chunked scan of a selective state-space recurrence (Mamba-2's SSD form)
over one whole sequence from a zero state (TPU): what a prefill computes.

The recurrence, per head ``h`` with ``B, C`` of its group ``g(h)`` and
``a_t = dt_t A``:

    S_t = exp(a_t) S_(t-1) + B_t (x) (dt_t x_t)       S [N, P], S_(-1) = 0
    y_t = S_t^T C_t

(the skip term ``D x`` is the caller's). In chunks of ``Q`` tokens, with
``s_t`` the running sum of ``a`` inside the chunk and ``S0`` the state that
enters it:

    y_t  = sum_(tau <= t) exp(s_t - s_tau) (C_t . B_tau) dt_tau x_tau
           + exp(s_t) S0^T C_t
    S0' = exp(s_end) S0 + sum_tau exp(s_end - s_tau) B_tau (x) dt_tau x_tau

so a chunk is four matrix products: ``C B^T [Q, Q]`` (once a group),
``(C B^T . L) (dt x) [Q, P]``, ``C S0 [Q, P]`` and ``(B w)^T (dt x)
[N, P]``. A position whose ``dt`` is 0 leaves the state as it is (decay 1,
input 0): that is how a caller pads.

TPU shape: grid (head blocks, chunks), the chunks in order; the state of
the block's heads lives in the second output's block, which stays in VMEM
while the chunk index runs (``[hb, N, P]`` float32) and is the final state
at the end. The products take ``x``'s dtype into the MXU (bf16 in serving)
and accumulate in float32; the decays and the carried state are float32.
What is cheap to lay out outside is laid out outside (XLA): ``dt x`` per
head, the running sums ``s`` (rows ``[H, T]``), ``B`` transposed
``[G, N, T]``. ``s`` as a column comes from one native transpose of its
sublane broadcast.

Selection policy as for the other kernels: Mosaic on a TPU, the
``jax.numpy`` form (the same chunks under ``lax.scan``) elsewhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode as _interpret_mode, use_pallas, x64_off

__all__ = ["ssd_chunk_scan", "ssd_chunk_scan_pallas", "ssd_chunk_scan_ref"]


def _precision(dtype):
    """float32 operands are multiplied as float32 (the tests hold the
    kernel to the sequential recurrence); bf16 goes to the MXU as it is."""
    return (jax.lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32
            else jax.lax.Precision.DEFAULT)


def _chunks(x, dt, A, B, C, chunk):
    """Pad to whole chunks (dt 0) and lay out what both forms take:
    ``xdt [H, T, P]`` in x's dtype, ``s [H, T]`` float32 (running sum of
    ``dt A`` inside each chunk), ``Bt [G, N, T]``, ``C [G, T, N]`` in x's
    dtype; and the unpadded length."""
    T = x.shape[0]
    Q = min(int(chunk), T)
    pad = -T % Q
    if pad:
        x, dt, B, C = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                       for a in (x, dt, B, C))
    dt = dt.astype(jnp.float32)
    xdt = (x.astype(jnp.float32) * dt[..., None]).astype(x.dtype)
    a = (dt * A.astype(jnp.float32)[None]).T                       # [H, T]
    s = jnp.cumsum(a.reshape(a.shape[0], -1, Q), axis=-1).reshape(a.shape)
    return (jnp.moveaxis(xdt, 1, 0), s,
            jnp.moveaxis(B, 0, 2).astype(x.dtype),
            jnp.moveaxis(C, 1, 0).astype(x.dtype), Q, T)


def _chunk_math(s, xdt, bt, c, s0, dot):
    """One head, one chunk. s ``[1, Q]``; xdt ``[Q, P]``; bt ``[N, Q]``;
    c ``[Q, N]``; s0 ``[N, P]`` float32; ``dot`` multiplies two matrices
    into float32. Returns (y ``[Q, P]`` float32, the state leaving)."""
    q = s.shape[1]
    srow = jnp.broadcast_to(s, (q, q))          # [t, tau] -> s_tau
    scol = jnp.transpose(srow)                  # [t, tau] -> s_t
    tri = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
           <= jax.lax.broadcasted_iota(jnp.int32, (q, q), 0))
    decay = jnp.where(tri, jnp.exp(jnp.where(tri, scol - srow, 0.0)), 0.0)
    m = (dot(c, bt) * decay).astype(xdt.dtype)
    y = dot(m, xdt) + jnp.exp(scol[:, :1]) * dot(c, s0.astype(xdt.dtype))
    s_end = s[:, q - 1:q]                                        # [1, 1]
    w = jnp.exp(s_end - s)                                       # [1, Q]
    # s_end down the sublanes first, then along the lanes (Mosaic lowers no
    # broadcast of one element in both at once)
    carry = jnp.exp(jnp.broadcast_to(s, (s0.shape[0], q))[:, q - 1:q])
    new = carry * s0 + dot(
        (bt.astype(jnp.float32) * w).astype(xdt.dtype), xdt)
    return y, new


def ssd_chunk_scan_ref(x, dt, A, B, C, *, chunk):
    """The plain form. x ``[T, H, P]``; dt ``[T, H]`` (after softplus; 0 at
    padding); A ``[H]`` (negative); B, C ``[T, G, N]``. Returns (y
    ``[T, H, P]`` float32, the final state ``[H, N, P]`` float32)."""
    H, P = x.shape[1:]
    G, N = B.shape[1:]
    xdt, s, bt, c, Q, T = _chunks(x, dt, A, B, C, chunk)
    nc = s.shape[1] // Q
    rep = H // G
    prec = _precision(x.dtype)

    def dot(a, b):
        return jnp.matmul(a, b, precision=prec,
                          preferred_element_type=jnp.float32)

    def head(s_h, xdt_h, bt_h, c_h, s0_h):
        return _chunk_math(s_h[None], xdt_h, bt_h, c_h, s0_h, dot)

    def step(state, inp):
        s_c, xdt_c, bt_c, c_c = inp
        y, state = jax.vmap(head)(
            s_c, xdt_c, jnp.repeat(bt_c, rep, axis=0),
            jnp.repeat(c_c, rep, axis=0), state)
        return state, y

    state, y = jax.lax.scan(
        step, jnp.zeros((H, N, P), jnp.float32),
        (jnp.moveaxis(s.reshape(H, nc, Q), 1, 0),
         jnp.moveaxis(xdt.reshape(H, nc, Q, P), 1, 0),
         jnp.moveaxis(bt.reshape(G, N, nc, Q), 2, 0),
         jnp.moveaxis(c.reshape(G, nc, Q, N), 1, 0)))
    # [nc, H, Q, P] -> [T, H, P]
    y = jnp.moveaxis(y, 1, 0).reshape(H, nc * Q, P)
    return jnp.moveaxis(y, 0, 1)[:T], state


def _scan_kernel(s_ref, xdt_ref, bt_ref, c_ref, y_ref, state_ref, *, prec):
    """Grid (head blocks, chunks). s_ref ``[hb, Q]``; xdt_ref, y_ref
    ``[hb, Q, P]``; bt_ref ``[N, Q]``, c_ref ``[Q, N]`` (the block's
    group); state_ref ``[hb, N, P]``, resident while the chunks run."""
    @pl.when(pl.program_id(1) == 0)
    def _zero():
        state_ref[...] = jnp.zeros_like(state_ref)

    def dot(a, b):
        return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32,
                                   precision=prec)

    bt, c = bt_ref[...], c_ref[...]
    for h in range(s_ref.shape[0]):
        y, new = _chunk_math(s_ref[h:h + 1, :], xdt_ref[h], bt, c,
                             state_ref[h], dot)
        y_ref[h] = y
        state_ref[h] = new


def _heads_per_block(heads, groups):
    """Heads a grid step: a divisor of a group's heads, whole sublane
    tiles of the ``[hb, Q]`` rows where the group allows."""
    per_group = heads // groups
    return 8 if per_group % 8 == 0 else per_group


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _scan_call(x, dt, A, B, C, *, chunk, interpret):
    H, P = x.shape[1:]
    G, N = B.shape[1:]
    xdt, s, bt, c, Q, T = _chunks(x, dt, A, B, C, chunk)
    Tp = s.shape[1]
    hb = _heads_per_block(H, G)
    per_group = H // G
    with x64_off():
        y, state = pl.pallas_call(
            functools.partial(_scan_kernel, prec=_precision(x.dtype)),
            grid=(H // hb, Tp // Q),
            in_specs=[
                pl.BlockSpec((hb, Q), lambda j, k: (j, k)),
                pl.BlockSpec((hb, Q, P), lambda j, k: (j, k, 0)),
                pl.BlockSpec((None, N, Q),
                             lambda j, k: (j * hb // per_group, 0, k)),
                pl.BlockSpec((None, Q, N),
                             lambda j, k: (j * hb // per_group, k, 0)),
            ],
            out_specs=(
                pl.BlockSpec((hb, Q, P), lambda j, k: (j, k, 0)),
                pl.BlockSpec((hb, N, P), lambda j, k: (j, 0, 0)),
            ),
            out_shape=(jax.ShapeDtypeStruct((H, Tp, P), jnp.float32),
                       jax.ShapeDtypeStruct((H, N, P), jnp.float32)),
            # the chunks run in order: the state's block carries
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
            name="ssd_chunk_scan",
        )(s, xdt, bt, c)
    return jnp.moveaxis(y, 0, 1)[:T], state


def ssd_chunk_scan_pallas(x, dt, A, B, C, *, chunk, interpret=None):
    """The Pallas kernel; see :func:`ssd_chunk_scan_ref` for the
    contract."""
    if interpret is None:
        interpret = _interpret_mode()
    return _scan_call(x, dt, A, B, C, chunk=int(chunk), interpret=interpret)


def ssd_chunk_scan(x, dt, A, B, C, *, chunk):
    """Policy entry: the kernel on a TPU, the plain form elsewhere."""
    if use_pallas():
        return ssd_chunk_scan_pallas(x, dt, A, B, C, chunk=chunk)
    return ssd_chunk_scan_ref(x, dt, A, B, C, chunk=chunk)
