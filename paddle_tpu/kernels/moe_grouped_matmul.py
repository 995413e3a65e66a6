"""Grouped matrix product for sparse experts (TPU).

A no-drop sparse-expert layer sorts its (token, expert) rows by expert and
multiplies each expert's run of rows by that expert's matrix:

    out[r] = lhs[r] @ rhs[g]   for   offsets[g] <= r < offsets[g + 1]

with ``offsets`` the running sum of ``group_sizes``. Rows past
``sum(group_sizes)`` (rows routed to experts that are not held here) come
out zero.

TPU shape: the rows are cut into tiles of ``tm``; a group's run of rows
does not end on a tile boundary, so the unit of work is a *visit*: one
(group, row tile) pair in which the group has rows. There are at most
``tiles + groups - 1`` of them; which group and which tile each visit is
rides in scalar prefetch (``pltpu.PrefetchScalarGridSpec``), and the block
index maps read it, so a weight tile of a group without rows is never
asked for, and consecutive visits of one group (a long run of rows) or of
one row tile (many short runs) find their block already in VMEM. A visit
multiplies the whole row tile by the group's ``[K, tn]`` weight tile and
keeps only its own rows of the result. The grid is (N tiles, visits): the
contraction is not tiled (an expert's ``K`` is its hidden or its
intermediate width, and a ``[K, tn]`` tile of some 2 MB fits VMEM twice).
Visits past the live ones repeat the last live visit's indices (no copy)
and compute nothing.

One kernel serves decode (a row or two a group, bound by the weights'
bytes: ``tm`` is the dtype's least row tile) and prefill (tens of rows a
group: ``tm`` 128). Selection policy as for the other kernels: Pallas on a
TPU, the plain ``jax.lax.ragged_dot`` form elsewhere and for shapes that
do not tile (``K`` or ``N`` not a multiple of a lane width).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode as _interpret_mode, use_pallas, x64_off

__all__ = ["moe_grouped_matmul", "moe_grouped_matmul_pallas",
           "moe_grouped_matmul_ref"]

_LANES = 128
# what one weight tile [K, tn] may take of VMEM (it is held twice)
_WEIGHT_TILE_BYTES = 2 * 1024 * 1024
# from this many rows a group on average, the row tile is MXU-sized
_PREFILL_ROWS_PER_GROUP = 8


def moe_grouped_matmul_ref(lhs, rhs, group_sizes):
    """The plain form. lhs [M, K] rows sorted by group, rhs [G, K, N],
    group_sizes int [G]; returns [M, N] in lhs's dtype, float32
    accumulation, zero rows past ``sum(group_sizes)``."""
    out = jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32),
                             preferred_element_type=jnp.float32)
    return out.astype(lhs.dtype)


def _tiles(m, k, n, num_groups, dtype):
    """(tm, tn) from the shapes alone."""
    itemsize = jnp.dtype(dtype).itemsize
    least = 8 * (4 // itemsize)           # 8 rows of 32 bits, packed
    tm = 128 if m >= _PREFILL_ROWS_PER_GROUP * num_groups else least
    tm = min(tm, -(-m // least) * least)
    tn = n
    while tn % (2 * _LANES) == 0 and k * tn * itemsize > _WEIGHT_TILE_BYTES:
        tn //= 2
    return tm, tn


def _visits(group_sizes, m_tiles, tm):
    """Which (group, row tile) each visit is: ``(group_ids, tile_ids,
    offsets, num_visits)``, int32, the first two of the static length
    ``m_tiles + groups - 1``."""
    g = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    first_tile = starts // tm
    n_tiles = jnp.where(sizes > 0, (ends + tm - 1) // tm - first_tile, 0)
    visit_ends = jnp.cumsum(n_tiles)
    num_visits = visit_ends[-1]
    visit = jnp.arange(m_tiles + g - 1, dtype=jnp.int32)
    # visits past the live ones repeat the last live one
    visit = jnp.minimum(visit, jnp.maximum(num_visits - 1, 0))
    group_ids = jnp.minimum(
        jnp.searchsorted(visit_ends, visit, side="right").astype(jnp.int32),
        g - 1)
    tile_ids = (first_tile[group_ids]
                + visit - (visit_ends - n_tiles)[group_ids])
    tile_ids = jnp.clip(tile_ids, 0, m_tiles - 1)
    return group_ids, tile_ids, offsets, num_visits.reshape(1)


def _gmm_kernel(gid_ref, tile_ref, off_ref, nv_ref, lhs_ref, rhs_ref, o_ref):
    """Grid (N tiles, visits). lhs_ref [tm, K]: the visit's row tile;
    rhs_ref [1, K, tn]: its group's weight tile; o_ref [tm, tn], which stays
    in VMEM while consecutive visits name the same row tile."""
    v = pl.program_id(1)
    tm = lhs_ref.shape[0]

    @pl.when(v < nv_ref[0])
    def _visit():
        g = gid_ref[v]
        # explicit DEFAULT: the package-wide tensorfloat32 default would ask
        # Mosaic for Precision.HIGH, which it does not lower
        acc = jax.lax.dot_general(
            lhs_ref[...], rhs_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)
        row = tile_ref[v] * tm + jax.lax.broadcasted_iota(
            jnp.int32, acc.shape, 0)
        mine = (row >= off_ref[g]) & (row < off_ref[g + 1])
        o_ref[...] = jnp.where(mine, acc.astype(o_ref.dtype), o_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gmm_call(lhs, rhs, group_sizes, *, interpret):
    m, k = lhs.shape
    g, _, n = rhs.shape
    tm, tn = _tiles(m, k, n, g, lhs.dtype)
    m_pad = -(-m // tm) * tm
    if m_pad != m:
        lhs = jnp.pad(lhs, ((0, m_pad - m), (0, 0)))
    m_tiles = m_pad // tm
    group_ids, tile_ids, offsets, num_visits = _visits(
        group_sizes, m_tiles, tm)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,   # group_ids, tile_ids, offsets, num_visits
        grid=(n // tn, m_tiles + g - 1),
        in_specs=[
            pl.BlockSpec((tm, k), lambda j, v, gid, tile, off, nv:
                         (tile[v], 0)),
            pl.BlockSpec((1, k, tn), lambda j, v, gid, tile, off, nv:
                         (gid[v], 0, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda j, v, gid, tile, off, nv:
                               (tile[v], j)),
    )
    with x64_off():
        out = pl.pallas_call(
            _gmm_kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((m_pad, n), lhs.dtype),
            # visits run in order: an output tile is finished over
            # consecutive visits
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
            name="moe_grouped_matmul",
        )(group_ids, tile_ids, offsets, num_visits, lhs, rhs)
    # rows no group owns were never written
    row = jnp.arange(m_pad, dtype=jnp.int32)[:, None]
    return jnp.where(row < offsets[-1], out, 0)[:m]


def moe_grouped_matmul_pallas(lhs, rhs, group_sizes, *, interpret=None):
    """The Pallas kernel; see :func:`moe_grouped_matmul_ref` for the
    contract. ``interpret`` defaults to the platform policy."""
    if interpret is None:
        interpret = _interpret_mode()
    return _gmm_call(lhs, rhs, group_sizes, interpret=interpret)


def moe_grouped_matmul(lhs, rhs, group_sizes):
    """Policy entry: the kernel on a TPU for shapes that tile, the plain
    form elsewhere."""
    k, n = rhs.shape[1:]
    if use_pallas() and k % _LANES == 0 and n % _LANES == 0:
        return moe_grouped_matmul_pallas(lhs, rhs, group_sizes)
    return moe_grouped_matmul_ref(lhs, rhs, group_sizes)
