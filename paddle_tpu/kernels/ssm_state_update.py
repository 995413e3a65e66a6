"""One token's step of a selective state-space recurrence (Mamba-2), the
state updated in place (TPU).

A serving engine keeps, for every running sequence and state-space layer,
the recurrent state of each head: ``state[layer, slot, h]`` is ``[N, P]``
float32 (``N`` state channels by ``P`` head channels; the transpose of the
``[P, N]`` the papers draw, so that a head's channels lie along the lanes
and the per-token vectors below broadcast along them without a relayout).
For one token a slot,

    S[h] <- exp(dt[h] A[h]) S[h] + B[g(h)] (x) (dt[h] x[h])
    y[h]  = S[h]^T C[g(h)]

with ``B, C [G, N]`` shared by the ``H / G`` heads of a group. The skip
term ``D x`` is the caller's.

TPU shape: byte-bound (a slot's state of a layer, ``H N P`` float32, is
read once and written once for ``6 H N P`` FLOPs). The state is the call's
operand whole, every layer of it, **aliased to an output**, the layer index
a prefetched scalar (as ``kernels/paged_attention.py`` holds the KV pool: a
step that calls the kernel once a layer on a donated state compiles to a
chain of custom calls on one buffer, no copy, slice or scatter of the
state). Grid (slots, head blocks): a block of ``hb`` heads' states comes to
VMEM and goes back through the pipeline's own copies while the next is in
flight. ``B`` and ``C`` arrive as rows ``[1, N]`` and are turned to
columns once a grid step (broadcast along sublanes, one native transpose);
everything else is elementwise on ``[N, P]`` tiles and a sum over
sublanes.

Every slot is stepped, live or not (an idle slot's row stays finite: the
decay is at most 1), so the engine's batch row is the state's row.

Selection policy as for the other kernels: Mosaic on a TPU, the
``jax.numpy`` form elsewhere (authoritative for the semantics; the kernel
runs in interpret mode in the tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode as _interpret_mode, use_pallas, x64_off

__all__ = ["ssm_state_update", "ssm_state_update_pallas",
           "ssm_state_update_ref"]

# what one block of states may take of VMEM (held four times: in and out,
# each double-buffered)
_BLOCK_BYTES = 1024 * 1024


def _terms(x, dt, A, B, C):
    """float32 ``decay [S, H]``, ``dtx [S, H, P]``, ``B, C [S, G, N]``."""
    dt = dt.astype(jnp.float32)
    decay = jnp.exp(dt * A.astype(jnp.float32)[None])
    dtx = dt[..., None] * x.astype(jnp.float32)
    return decay, dtx, B.astype(jnp.float32), C.astype(jnp.float32)


def ssm_state_update_ref(state, layer_idx, x, dt, A, B, C):
    """The plain form. state ``[L, S, H, N, P]`` float32; x ``[S, H, P]``;
    dt ``[S, H]`` (after softplus); A ``[H]`` (negative); B, C
    ``[S, G, N]``. Returns (y ``[S, H, P]`` float32, state)."""
    heads = x.shape[1]
    decay, dtx, B, C = _terms(x, dt, A, B, C)
    rep = heads // B.shape[1]
    Bh, Ch = jnp.repeat(B, rep, axis=1), jnp.repeat(C, rep, axis=1)
    new = (decay[..., None, None] * state[layer_idx]
           + Bh[..., :, None] * dtx[..., None, :])
    y = jnp.sum(new * Ch[..., :, None], axis=-2)
    return y, state.at[layer_idx].set(new.astype(state.dtype))


def _heads_per_block(heads, groups, n, p):
    """Heads a grid step, from the shapes alone: a divisor of a group's
    heads, whole sublane tiles of the ``[hb, P]`` rows, within the block's
    bytes."""
    per_group = heads // groups
    hb = per_group
    while hb % 16 == 0 and hb * n * p * 4 > _BLOCK_BYTES:
        hb //= 2
    return hb


def _update_kernel(layer_ref, decay_ref, dtx_ref, b_ref, c_ref, s_ref,
                   y_ref, o_ref):
    """decay_ref, dtx_ref, y_ref ``[hb, P]``; b_ref, c_ref ``[1, N]`` (the
    block's group); s_ref, o_ref ``[hb, N, P]``."""
    del layer_ref
    hb, n, p = s_ref.shape
    # rows to columns: [1, N] along sublanes to [P, N], then one transpose
    bt = jnp.transpose(jnp.broadcast_to(b_ref[...], (p, n)))      # [N, P]
    ct = jnp.transpose(jnp.broadcast_to(c_ref[...], (p, n)))
    for h in range(hb):
        new = (decay_ref[h:h + 1, :] * s_ref[h].astype(jnp.float32)
               + bt * dtx_ref[h:h + 1, :])
        o_ref[h] = new.astype(o_ref.dtype)
        y_ref[h:h + 1, :] = jnp.sum(new * ct, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _update_call(layer, decay, dtx, B, C, state, *, interpret):
    """layer int32 ``[1]``; decay, dtx ``[S, H, P]`` float32 (the decay
    repeated along P); B, C ``[S, G, 1, N]`` float32; state
    ``[L, S, H, N, P]``. Returns (y, state), the state aliased to the
    operand. Jitted so that a step that calls it once a layer traces and
    lowers the kernel once."""
    S, H, P = dtx.shape
    G, N = B.shape[1], B.shape[3]
    hb = _heads_per_block(H, G, N, P)
    per_group = H // G
    row = pl.BlockSpec((None, hb, P), lambda s, j, layer: (s, j, 0))
    group = pl.BlockSpec((None, None, 1, N),
                         lambda s, j, layer: (s, j * hb // per_group, 0, 0))
    block = pl.BlockSpec((None, None, hb, N, P),
                         lambda s, j, layer: (layer[0], s, j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(S, H // hb),
        in_specs=[row, row, group, group, block],
        out_specs=(row, block),
    )
    with x64_off():
        return pl.pallas_call(
            _update_kernel,
            grid_spec=grid_spec,
            out_shape=(jax.ShapeDtypeStruct((S, H, P), jnp.float32),
                       jax.ShapeDtypeStruct(state.shape, state.dtype)),
            # the last operand is the state: written in place, handed on as
            # output 1; a block is read before it is written and no other
            # grid step touches it
            input_output_aliases={5: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=interpret,
            name="ssm_state_update",
        )(layer, decay, dtx, B, C, state)


def ssm_state_update_pallas(state, layer_idx, x, dt, A, B, C, *,
                            interpret=None):
    """The Pallas kernel, in place; see :func:`ssm_state_update_ref` for
    the contract. The returned state is the operand's buffer: a caller that
    owns it (the engine donates it to the step) pays no copy."""
    if interpret is None:
        interpret = _interpret_mode()
    decay, dtx, B, C = _terms(x, dt, A, B, C)
    return _update_call(
        jnp.full((1,), layer_idx, jnp.int32),
        jnp.broadcast_to(decay[..., None], dtx.shape), dtx,
        B[:, :, None, :], C[:, :, None, :], state, interpret=interpret)


def ssm_state_update(state, layer_idx, x, dt, A, B, C):
    """Policy entry: the kernel on a TPU, the plain form elsewhere."""
    if use_pallas():
        return ssm_state_update_pallas(state, layer_idx, x, dt, A, B, C)
    return ssm_state_update_ref(state, layer_idx, x, dt, A, B, C)
