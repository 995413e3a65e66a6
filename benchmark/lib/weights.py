"""Weights from ``--seed``, made on the device in one jitted call.

The benchmark, not the program, makes the weights: the driver puts them into
the program's model, and the plain reference is given the same function and
the same seed, so nothing the program has made reaches the reference.

``shapes`` is what the configuration's architecture gives
(``arch/<name>.py::shapes(cfg)``): the leaves' names and shapes in a fixed
order. A leaf with one axis is a norm weight, any other a matrix.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

INIT_STD = 0.02          # matrices: normal(0, 0.02), as the family initialises
NORM_JITTER = 0.1        # norm weights: 1 + 0.1 normal, so that they matter


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**32 and past it."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed >> 16), seed & 0xFFFF)


def _leaf(key, index, shape, dtype):
    k = jax.random.fold_in(key, index)
    x = jax.random.normal(k, shape, jnp.float32)
    if len(shape) == 1:
        return (1.0 + NORM_JITTER * x).astype(dtype)
    return (INIT_STD * x).astype(dtype)


def build_flat(key, shapes, dtype, names=None):
    """Traceable: the leaves (all, or ``names``) from a key. The value of a
    leaf depends on the key and on its place in ``shapes`` alone."""
    index = {n: i for i, n in enumerate(shapes)}
    dtype = jnp.dtype(dtype)
    return {n: _leaf(key, index[n], shapes[n], dtype)
            for n in (shapes if names is None else names)}


def make_weights(shapes, seed, dtype, names=None):
    """All leaves (or ``names``) in one jitted call."""
    names = None if names is None else tuple(names)
    return jax.jit(lambda key: build_flat(key, shapes, dtype, names))(
        seed_key(seed))
