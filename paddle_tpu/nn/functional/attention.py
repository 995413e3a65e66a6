"""Attention functionals.

Parity: /root/reference/python/paddle/nn/functional/flash_attention.py (the
reference vendors flash-attn CUDA kernels, third_party/flashattn) and
scaled_dot_product_attention. On TPU the default path is plain einsum
attention that XLA fuses well at moderate sequence lengths; the Pallas
flash/splash kernel in paddle_tpu.kernels registers over the same entry
point for long sequences (selected by ``paddle_tpu.kernels.use_pallas``).
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ...core.dispatch import apply

__all__ = ["scaled_dot_product_attention", "flash_attention",
           "flash_attn_unpadded", "sdpa_ref", "CacheLayer", "StateLayer",
           "causal_window_mask"]


class CacheLayer(NamedTuple):
    """What one attention layer keeps in a KV cache: a model's
    ``cache_layers()`` gives one a layer, and whoever holds the cache (the
    serving engine) sizes it and tells it each layer's window from them,
    whatever the model."""

    kv_heads: int
    head_dim: int
    window: int | None = None     # latest positions a query sees, or all


class StateLayer(NamedTuple):
    """What one recurrent (state-space) layer keeps for each running
    sequence, whatever its length: the recurrence's carry and the last
    inputs of its causal conv, each as ``(shape, dtype)``, a dtype of None
    being the cache's own (the model's). A model's ``cache_layers()`` gives
    one beside its ``CacheLayer``s; its attention layers count themselves
    among the ``CacheLayer``s and its recurrent layers among these."""

    state: tuple                  # ((heads, d_state, head_dim), "float32")
    conv: tuple                   # ((d_conv - 1, channels), None)


def causal_window_mask(sq, sk, window=None):
    """bool [sq, sk]: the last ``sq`` of ``sk`` positions as queries over all
    ``sk`` as keys; query ``s`` sees key ``t`` iff ``s - window < t <= s``
    (``t <= s`` without a window)."""
    qi = jnp.arange(sk - sq, sk, dtype=jnp.int32)[:, None]
    kj = jnp.arange(sk, dtype=jnp.int32)[None, :]
    mask = kj <= qi
    if window is not None:
        mask &= kj > qi - window
    return mask


def sdpa_ref(q, k, v, attn_mask=None, dropout_p=0.0, is_causal=False,
             scale=None, training=True, **_ignored):
    """Reference einsum attention on raw arrays, [B, S, H, D] layout (paddle's
    flash_attention layout). GQA supported: Hk may divide Hq. Dropout is
    applied to the softmax probabilities (upscale-in-train), matching the
    reference's _math_attention
    (/root/reference/python/paddle/nn/functional/flash_attention.py)."""
    B, Sq, Hq, D = q.shape
    Sk, Hk = k.shape[1], k.shape[2]
    if Hk != Hq:
        rep = Hq // Hk
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    # [B, H, Sq, Sk]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if is_causal:
        mask = jnp.tril(jnp.ones((Sq, Sk), bool), k=Sk - Sq)
        logits = jnp.where(mask, logits, -1e30)
    if attn_mask is not None:
        if attn_mask.dtype == jnp.bool_:
            logits = jnp.where(attn_mask, logits, -1e30)
        else:
            logits = logits + attn_mask
    # promote (never downcast): f32 softmax for bf16/f16 inputs, but f64
    # inputs keep f64 (the FD grad gate runs this op in float64)
    acc_t = jnp.promote_types(logits.dtype, jnp.float32)
    probs = jax.nn.softmax(logits.astype(acc_t), axis=-1).astype(q.dtype)
    if dropout_p and training:
        fixed_seed = _ignored.get("fixed_seed")
        if fixed_seed is not None:
            key = jax.random.PRNGKey(int(fixed_seed))
        else:
            from ...framework.random import next_key

            key = next_key()
        keep = jax.random.bernoulli(key, 1.0 - dropout_p, probs.shape)
        probs = probs * keep.astype(probs.dtype) / (1.0 - dropout_p)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False, training=True,
                                 name=None, scale=None):
    """paddle layout [batch, seq, heads, head_dim]."""
    from ...kernels import attention_impl

    impl = attention_impl()

    def body(q, k, v, m=None):
        return impl(q, k, v, attn_mask=m, dropout_p=dropout_p,
                    is_causal=is_causal, scale=scale, training=training)

    if attn_mask is None:
        return apply(body, query, key, value, op_name="sdpa")
    return apply(body, query, key, value, attn_mask, op_name="sdpa")


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None, rng_name="",
                    training=True, name=None):
    """reference flash_attention API shape: returns (out, softmax?)."""
    out = scaled_dot_product_attention(
        query, key, value, dropout_p=dropout, is_causal=causal, training=training)
    return (out, None) if return_softmax else (out, None)


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q=None, max_seqlen_k=None, scale=None,
                        dropout=0.0, causal=False, return_softmax=False,
                        fixed_seed_offset=None, rng_name="", training=True,
                        name=None):
    """Varlen (packed-sequence) flash attention over [total_tokens, H, D]
    inputs with cu_seqlens offsets. Parity: flash_attn_unpadded
    (/root/reference/python/paddle/nn/functional/flash_attention.py:272).
    Runs the Pallas segment-ids kernel with cross-sequence block skipping;
    interpret mode (CPU) runs the same kernel under the Pallas interpreter."""
    from ...kernels.flash_attention import flash_attn_varlen_pallas

    def body(q, k, v, cq, ck):
        return flash_attn_varlen_pallas(
            q, k, v, cq, ck, max_seqlen_q, max_seqlen_k, scale=scale,
            dropout_p=dropout, causal=causal, training=training,
            fixed_seed=fixed_seed_offset)

    out = apply(body, query, key, value, cu_seqlens_q, cu_seqlens_k,
                op_name="flash_attn_unpadded")
    return (out, None) if return_softmax else (out, None)
