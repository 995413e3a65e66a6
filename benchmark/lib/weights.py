"""Weights from ``--seed``, made on the device in one jitted call.

The benchmark, not the program, makes the weights: the driver puts them into
the program's model, and the plain reference is given the same function and
the same seed, so nothing the program has made reaches the reference.

Names and layout (the benchmark's own; ``[in, out]`` matrices, q|k|v and
gate|up fused along ``out``): ``embed_tokens.weight [V, H]``,
``layers.<i>.input_layernorm.weight [H]``,
``layers.<i>.self_attn.qkv_proj.weight [H, (Hq + 2 Hkv) D]``,
``layers.<i>.self_attn.o_proj.weight [Hq D, H]``,
``layers.<i>.post_attention_layernorm.weight [H]``,
``layers.<i>.mlp.gate_up_proj.weight [H, 2 I]``,
``layers.<i>.mlp.down_proj.weight [I, H]``, ``norm.weight [H]``,
``lm_head.weight [H, V]``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import work

INIT_STD = 0.02          # matrices: normal(0, 0.02), as the family initialises
NORM_JITTER = 0.1        # norm weights: 1 + 0.1 normal, so that they matter


def shapes(cfg):
    h, d = cfg["hidden_size"], work.head_dim(cfg)
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    inter, v = cfg["intermediate_size"], cfg["vocab_size"]
    out = {"embed_tokens.weight": (v, h)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"layers.{i}."
        out[p + "input_layernorm.weight"] = (h,)
        out[p + "self_attn.qkv_proj.weight"] = (h, (hq + 2 * hkv) * d)
        out[p + "self_attn.o_proj.weight"] = (hq * d, h)
        out[p + "post_attention_layernorm.weight"] = (h,)
        out[p + "mlp.gate_up_proj.weight"] = (h, 2 * inter)
        out[p + "mlp.down_proj.weight"] = (inter, h)
    out["norm.weight"] = (h,)
    out["lm_head.weight"] = (h, v)
    return out


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


def build_flat(key, cfg, dtype, names=None):
    """Traceable: the leaves (all, or ``names``) from a key. The value of a
    leaf depends on the key and on its place in ``shapes(cfg)`` alone."""
    all_shapes = shapes(cfg)
    index = {n: i for i, n in enumerate(all_shapes)}
    dtype = jnp.dtype(dtype)
    return {n: _leaf(key, index[n], all_shapes[n], dtype)
            for n in (all_shapes if names is None else names)}


def make_weights(cfg, seed, dtype, names=None):
    """All leaves (or ``names``) in one jitted call."""
    names = None if names is None else tuple(names)
    return jax.jit(lambda key: build_flat(key, cfg, dtype, names))(
        seed_key(seed))
