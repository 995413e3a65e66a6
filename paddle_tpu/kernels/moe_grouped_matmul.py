"""The sparse experts' gated MLP over rows grouped by expert (TPU).

A no-drop sparse-expert layer sorts its (token, expert) rows by expert and
passes each expert's run of rows through that expert's gated MLP:

    out[r] = (silu(lhs[r] @ gate[g]) * (lhs[r] @ up[g])) @ down[g]
             for offsets[g] <= r < offsets[g + 1]

with ``offsets`` the running sum of ``group_sizes`` and ``gate | up`` the
two halves of the stacked ``gate_up [G, K, 2 I]``. Rows past
``sum(group_sizes)`` (rows routed to experts that are not held here) come
out zero.

TPU shape: one Mosaic call, ``moe_grouped_matmul``, for the whole MLP. The
rows are cut into tiles of ``tm``; a group's run of rows does not end on a
tile boundary, so the unit of work is a *visit*: one (group, row tile) pair
in which the group has rows. There are at most ``tiles + groups - 1`` of
them; which group and which tile each visit is rides in scalar prefetch
(``pltpu.PrefetchScalarGridSpec``). The row tiles come through the grid's
block pipeline, so consecutive visits of one row tile (many short runs)
find it in VMEM. The weights stay in HBM and the kernel streams them itself
(:func:`_fetches`): a group without rows is never read, consecutive visits
of one group (a long run of rows) share one fetch, and each fetch is
started while the one before is in use, so the copies run back to back
(the byte floor is what bounds this kernel). A visit forms
``silu(gate) * up`` from the float32 products in VMEM, rounds it once to
the weights' dtype, multiplies it by ``down`` and keeps only its own rows
of the ``[tm, K]`` result. The grid is (visits, intermediate tiles): an
expert whose three weight tiles fit ``_WEIGHT_BLOCK_BYTES`` (Laguna-XS.2's,
6 MiB) is one tile wide, so a visit is one grid step; a wider one is cut
along its intermediate width, which splits all three tiles alike, and its
tiles' products are summed in float32. Visits past the live ones repeat
the last live visit's row tile and compute nothing.

One kernel serves decode (a row or two a group, bound by the weights'
bytes: ``tm`` is the dtype's least row tile) and prefill (tens of rows a
group: ``tm`` 128). Selection policy as for the other kernels: Pallas on a
TPU, the plain form (two ``jax.lax.ragged_dot`` products) elsewhere and for
widths that do not tile (``K`` or ``I`` not a multiple of a lane width).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode as _interpret_mode, use_pallas, x64_off

__all__ = ["moe_gated_mlp_impl", "moe_gated_mlp_pallas", "moe_gated_mlp_ref",
           "moe_grouped_matmul_ref"]

_LANES = 128
# what one fetch's gate, up and down tiles may take of VMEM together (they
# are held twice, the next fetch arriving while this one is in use)
_WEIGHT_BLOCK_BYTES = 8 * 1024 * 1024
# from this many rows a group on average, the row tile is MXU-sized
_PREFILL_ROWS_PER_GROUP = 8


def moe_grouped_matmul_ref(lhs, rhs, group_sizes):
    """One grouped product, the plain form. lhs [M, K] rows sorted by
    group, rhs [G, K, N], group_sizes int [G]; returns [M, N] in lhs's
    dtype, float32 accumulation, zero rows past ``sum(group_sizes)``."""
    out = jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32),
                             preferred_element_type=jnp.float32)
    return out.astype(lhs.dtype)


def moe_gated_mlp_ref(lhs, gate_up, down, group_sizes):
    """The plain form: two grouped products, the gate and up halves
    rounded to lhs's dtype between them. lhs [M, K] rows sorted by group,
    gate_up [G, K, 2 I], down [G, I, K], group_sizes int [G]; returns
    [M, K] in lhs's dtype, zero rows past ``sum(group_sizes)``."""
    gate, up = jnp.split(moe_grouped_matmul_ref(lhs, gate_up, group_sizes),
                         2, axis=-1)
    return moe_grouped_matmul_ref(jax.nn.silu(gate) * up, down, group_sizes)


def _tiles(m, k, i, num_groups, dtype):
    """(tm, ti): the row tile and the intermediate tile, from the shapes
    alone. ``ti`` is the widest multiple of the lane width that divides
    ``i`` and whose three weight tiles fit ``_WEIGHT_BLOCK_BYTES``."""
    itemsize = jnp.dtype(dtype).itemsize
    least = 8 * (4 // itemsize)           # 8 rows of 32 bits, packed
    tm = 128 if m >= _PREFILL_ROWS_PER_GROUP * num_groups else least
    tm = min(tm, -(-m // least) * least)
    if i % _LANES:                        # not for the chip: one tile
        return tm, i
    lanes = i // _LANES
    ti = next(_LANES * c for c in range(lanes, 0, -1)
              if lanes % c == 0 and (
                  c == 1 or 3 * k * _LANES * c * itemsize
                  <= _WEIGHT_BLOCK_BYTES))
    return tm, ti


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


def _dot(a, b):
    # explicit DEFAULT: the package-wide tensorfloat32 default would ask
    # Mosaic for Precision.HIGH, which it does not lower
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=jax.lax.Precision.DEFAULT)


def _fetches(group_ids, num_visits, nt):
    """The weight stream: which fetch of (group, intermediate tile) each
    visit starts with, ``visit_fetch`` (a step (v, t) uses fetch
    ``visit_fetch[v] + t``); the group of each run of fetches,
    ``run_group`` (fetch ``f`` is tile ``f % nt`` of ``run_group[f //
    nt]``); and how many fetches the live visits make, ``[1]``. With the
    expert one tile wide, consecutive visits of one group share one fetch;
    cut into tiles, every step fetches."""
    v = jnp.arange(group_ids.shape[0], dtype=jnp.int32)
    live = v < num_visits[0]
    new = live & ((v == 0) | (nt > 1)
                  | (group_ids != jnp.roll(group_ids, 1)))
    run = jnp.cumsum(new.astype(jnp.int32)) - 1
    run_group = jnp.zeros_like(group_ids).at[
        jnp.where(new, run, group_ids.shape[0])].set(group_ids, mode="drop")
    runs = jnp.sum(new.astype(jnp.int32))
    visit_fetch = jnp.maximum(run, 0) * nt
    return (visit_fetch.astype(jnp.int32), run_group,
            (runs * nt).astype(jnp.int32).reshape(1))


def _mlp_kernel(gid_ref, tile_ref, off_ref, nv_ref, vf_ref, rg_ref, nf_ref,
                x_ref, gu_hbm, d_hbm, o_ref, wg_buf, wu_buf, wd_buf, sems,
                *acc_ref):
    """Grid (visits, intermediate tiles). x_ref [tm, K]: the visit's row
    tile; gu_hbm, d_hbm: the stacked weights, left in HBM; wg_buf, wu_buf
    [2, K, ti] and wd_buf [2, ti, K]: two slots of the weight stream; o_ref
    [tm, K], which stays in VMEM while consecutive visits name the same row
    tile; acc_ref, where the intermediate width is cut, the float32 sum of
    the tiles' products.

    The weights are copied by hand, one fetch ahead of the fetch in use:
    the first step that uses fetch ``f`` starts ``f + 1`` into the other
    slot, then waits for ``f``. So the copies run back to back, also under
    the steps that need no new weights (a group's second row tile), where a
    block pipeline, which fetches only for the next grid step, would leave
    the copy engine idle and the step's products exposed."""
    v, t = pl.program_id(0), pl.program_id(1)
    tm = x_ref.shape[0]
    ti = wd_buf.shape[1]
    nt = gu_hbm.shape[2] // (2 * ti)

    def fetch(f, slot):
        g, j = rg_ref[f // nt], f % nt
        return (pltpu.make_async_copy(gu_hbm.at[g, :, pl.ds(j * ti, ti)],
                                      wg_buf.at[slot], sems.at[slot, 0]),
                pltpu.make_async_copy(
                    gu_hbm.at[g, :, pl.ds((nt + j) * ti, ti)],
                    wu_buf.at[slot], sems.at[slot, 1]),
                pltpu.make_async_copy(d_hbm.at[g, pl.ds(j * ti, ti), :],
                                      wd_buf.at[slot], sems.at[slot, 2]))

    @pl.when(v < nv_ref[0])
    def _visit():
        f = vf_ref[v] + t
        slot = f % 2
        prev = vf_ref[jnp.maximum(v - 1, 0)]

        @pl.when((v == 0) | (t > 0) | (vf_ref[v] != prev))
        def _first_use():
            @pl.when(f == 0)
            def _():
                for c in fetch(0, 0):
                    c.start()

            @pl.when(f + 1 < nf_ref[0])
            def _():
                for c in fetch(f + 1, 1 - slot):
                    c.start()

            for c in fetch(f, slot):
                c.wait()

        x = x_ref[...]
        gate, up = _dot(x, wg_buf[slot]), _dot(x, wu_buf[slot])
        # rounded once, as the down product's operand
        act = (gate * jax.nn.sigmoid(gate) * up).astype(wd_buf.dtype)
        y = _dot(act, wd_buf[slot])

        def keep_mine(y):
            g = gid_ref[v]
            row = tile_ref[v] * tm + jax.lax.broadcasted_iota(
                jnp.int32, y.shape, 0)
            mine = (row >= off_ref[g]) & (row < off_ref[g + 1])
            o_ref[...] = jnp.where(mine, y.astype(o_ref.dtype), o_ref[...])

        if not acc_ref:
            keep_mine(y)
            return
        acc, = acc_ref

        @pl.when(t == 0)
        def _():
            acc[...] = y

        @pl.when(t > 0)
        def _():
            acc[...] += y

        @pl.when(t == nt - 1)
        def _():
            keep_mine(acc[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _mlp_call(lhs, gate_up, down, group_sizes, *, interpret):
    m, k = lhs.shape
    g, i, _ = down.shape
    tm, ti = _tiles(m, k, i, g, lhs.dtype)
    nt = i // ti
    m_pad = -(-m // tm) * tm
    if m_pad != m:
        lhs = jnp.pad(lhs, ((0, m_pad - m), (0, 0)))
    m_tiles = m_pad // tm
    group_ids, tile_ids, offsets, num_visits = _visits(
        group_sizes, m_tiles, tm)
    scalars = (group_ids, tile_ids, offsets, num_visits,
               *_fetches(group_ids, num_visits, nt))

    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    rows = pl.BlockSpec((tm, k), lambda v, t, gid, tile, *_: (tile[v], 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(m_tiles + g - 1, nt),
        in_specs=[rows, in_hbm, in_hbm],
        out_specs=rows,
        scratch_shapes=[
            pltpu.VMEM((2, k, ti), down.dtype),
            pltpu.VMEM((2, k, ti), down.dtype),
            pltpu.VMEM((2, ti, k), down.dtype),
            pltpu.SemaphoreType.DMA((2, 3)),
        ] + ([pltpu.VMEM((tm, k), jnp.float32)] if nt > 1 else []),
    )
    wsize = jnp.dtype(down.dtype).itemsize
    xsize = jnp.dtype(lhs.dtype).itemsize
    # two slots of weights, two of each row block (the pipeline's next one
    # arriving), the float32 products and the sum
    vmem = (2 * (3 * k * ti * wsize + 2 * tm * k * xsize)
            + 4 * tm * (2 * ti + k + (k if nt > 1 else 0)))
    with x64_off():
        out = pl.pallas_call(
            _mlp_kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((m_pad, k), lhs.dtype),
            # visits run in order: an output tile is finished over
            # consecutive visits, and the weight stream carries from one
            # step to the next
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=vmem + vmem // 2 + (4 << 20)),
            interpret=interpret,
            # the name the benchmark's readers of the sparse experts find
            name="moe_grouped_matmul",
        )(*scalars, lhs, gate_up, down)
    # rows no group owns were never written
    row = jnp.arange(m_pad, dtype=jnp.int32)[:, None]
    return jnp.where(row < offsets[-1], out, 0)[:m]


def moe_gated_mlp_pallas(lhs, gate_up, down, group_sizes, *,
                         interpret=None):
    """The Pallas kernel; see :func:`moe_gated_mlp_ref` for the contract
    (the gate and up products stay float32 up to their product, which is
    rounded once). ``interpret`` defaults to the platform policy."""
    if interpret is None:
        interpret = _interpret_mode()
    return _mlp_call(lhs, gate_up, down, group_sizes, interpret=interpret)


def moe_gated_mlp_impl(d_model, d_expert):
    """Policy: the kernel on a TPU for widths that tile, the plain form
    elsewhere; both take ``(lhs, gate_up, down, group_sizes)``."""
    if use_pallas() and d_model % _LANES == 0 and d_expert % _LANES == 0:
        return moe_gated_mlp_pallas
    return moe_gated_mlp_ref
