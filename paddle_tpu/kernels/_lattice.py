"""Shared helpers for the sequence-lattice Pallas kernels (ctc.py, rnnt.py).

Both kernels use the same layout conventions — batch rows on sublanes
([8, lanes] vreg tiles), -1e30 as the log-space "-inf" sentinel, explicit
i32/f32 constants for the jax_enable_x64 Mosaic traps — so the encoding of
those conventions lives once, here.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

NEG = -1.0e30
BT = 8  # batch rows per grid program (one sublane tile)


def neg32():
    return jnp.float32(NEG)


def i0():
    # index-map constants must be i32: under jax_enable_x64 a python literal
    # traces as i64 and Mosaic rejects the mixed index tuple
    return jnp.int32(0)


def lanes(s: int) -> int:
    return max(128, ((s + 127) // 128) * 128)


def shift_right(a, k, lane, fill=None):
    f = neg32() if fill is None else fill
    return jnp.where(lane < k, f, pltpu.roll(a, jnp.int32(k), axis=1))


def shift_left(a, k, lane, size, fill=None):
    # pltpu.roll is circular with non-negative shift: left-by-k == size-k
    f = neg32() if fill is None else fill
    return jnp.where(lane >= size - k, f,
                     pltpu.roll(a, jnp.int32(size - k), axis=1))
