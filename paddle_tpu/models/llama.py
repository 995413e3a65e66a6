"""Llama-2 family — the flagship model (BASELINE config #4, ≥45% MFU target).

Structure parity with the reference Fleet Llama recipes (the reference trains
Llama via fleet DP×TP×PP with VocabParallelEmbedding / ColumnParallelLinear /
RowParallelLinear — /root/reference/python/paddle/distributed/fleet/layers/
mpu/mp_layers.py); architecture is standard Llama-2: RMSNorm, RoPE, GQA
attention, SwiGLU MLP.

TPU-first:
- TP via sharding annotations on the mp axis (GSPMD inserts collectives),
- attention through paddle_tpu.kernels (Pallas flash attention on TPU),
- pipeline via homogeneous-block stacking + spmd_pipeline,
- bf16 activations with f32 norms/softmax.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp

from .. import nn
from ..distributed.mp_layers import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
    mark_sharding,
)
from ..nn import functional as F
from ..nn.functional.attention import CacheLayer
from ..ops import manipulation as M

__all__ = ["LlamaConfig", "LlamaForCausalLM", "LlamaDecoderLayer",
           "llama_tiny", "llama_7b", "apply_rope", "apply_rope_at"]


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads


def llama_7b():
    return LlamaConfig()


def llama_tiny(vocab=256, hidden=64, layers=4, heads=4, kv_heads=2, inter=128, seq=128):
    return LlamaConfig(
        vocab_size=vocab, hidden_size=hidden, intermediate_size=inter,
        num_hidden_layers=layers, num_attention_heads=heads,
        num_key_value_heads=kv_heads, max_position_embeddings=seq)


def _rope_tables(head_dim, max_seq, theta, dtype=jnp.float32):
    inv_freq = 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))
    t = np.arange(max_seq)
    freqs = np.outer(t, inv_freq)  # [S, D/2]
    return jnp.asarray(np.cos(freqs), dtype), jnp.asarray(np.sin(freqs), dtype)


def apply_rope(x, cos, sin):
    """x: [B, S, H, D]; rotate-half RoPE."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    cos = cos[None, : x.shape[1], None, :]
    sin = sin[None, : x.shape[1], None, :]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


def apply_rope_at(x, cos, sin, positions):
    """RoPE at explicit token positions (cached decode: the new token sits
    mid-sequence, not at index 0). positions: int [B, S] or [S]."""
    d2 = x.shape[-1] // 2
    if positions.ndim == 1:
        positions = positions[None]
    c = cos[positions][:, :, None, :]   # [B, S, 1, D/2]
    s = sin[positions][:, :, None, :]
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate(
        [x1 * c - x2 * s, x2 * c + x1 * s], axis=-1
    ).astype(x.dtype)


class LlamaAttention(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        c = config
        self.num_heads = c.num_attention_heads
        self.num_kv_heads = c.num_key_value_heads
        self.head_dim = c.head_dim
        # fused qkv with mp-sharded output columns
        qkv_out = (c.num_attention_heads + 2 * c.num_key_value_heads) * c.head_dim
        self.qkv_proj = ColumnParallelLinear(c.hidden_size, qkv_out,
                                             has_bias=False, gather_output=False)
        self.o_proj = RowParallelLinear(c.num_attention_heads * c.head_dim,
                                        c.hidden_size, has_bias=False,
                                        input_is_parallel=True)
        self.config = c
        self.layer_idx = 0  # set by LlamaForCausalLM for KV-cache routing

    def forward(self, x, rope_cos, rope_sin, cache=None, positions=None):
        B, S = x.shape[0], x.shape[1]
        qkv = self.qkv_proj(x)
        q_sz = self.num_heads * self.head_dim
        kv_sz = self.num_kv_heads * self.head_dim
        q, k, v = M.split(qkv, [q_sz, kv_sz, kv_sz], axis=-1)
        q = M.reshape(q, [B, S, self.num_heads, self.head_dim])
        k = M.reshape(k, [B, S, self.num_kv_heads, self.head_dim])
        v = M.reshape(v, [B, S, self.num_kv_heads, self.head_dim])
        # heads sharded over mp
        q = mark_sharding(q, None, None, "mp", None)
        k = mark_sharding(k, None, None, "mp", None)
        v = mark_sharding(v, None, None, "mp", None)
        from ..core.dispatch import apply as _apply

        if positions is None:
            q = _apply(apply_rope, q, rope_cos, rope_sin, op_name="rope")
            k = _apply(apply_rope, k, rope_cos, rope_sin, op_name="rope")
        else:
            q = _apply(apply_rope_at, q, rope_cos, rope_sin, positions,
                       op_name="rope")
            k = _apply(apply_rope_at, k, rope_cos, rope_sin, positions,
                       op_name="rope")
        if cache is None:
            out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        else:
            # duck-typed KV-cache hook (serving.DenseKVCache /
            # serving.PagedCacheView): the cache absorbs this layer's new
            # K/V and returns attention over the full context
            import functools

            out = _apply(functools.partial(cache.attend, self.layer_idx),
                         q, k, v, op_name="kv_cached_attention")
        out = M.reshape(out, [B, S, self.num_heads * self.head_dim])
        return self.o_proj(out)


class LlamaMLP(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        c = config
        # fused gate+up (2x intermediate), SwiGLU
        self.gate_up_proj = ColumnParallelLinear(
            c.hidden_size, 2 * c.intermediate_size, has_bias=False, gather_output=False)
        self.down_proj = RowParallelLinear(
            c.intermediate_size, c.hidden_size, has_bias=False, input_is_parallel=True)
        self.inter = c.intermediate_size

    def forward(self, x):
        gate_up = self.gate_up_proj(x)
        gate, up = M.split(gate_up, 2, axis=-1)
        return self.down_proj(F.silu(gate) * up)


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.mlp = LlamaMLP(config)

    def forward(self, x, rope_cos, rope_sin, cache=None, positions=None):
        h = x + self.self_attn(self.input_layernorm(x), rope_cos, rope_sin,
                               cache=cache, positions=positions)
        return h + self.mlp(self.post_attention_layernorm(h))


class LlamaForCausalLM(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size, config.hidden_size)
        self.layers = nn.LayerList(
            [LlamaDecoderLayer(config) for _ in range(config.num_hidden_layers)])
        for i, layer in enumerate(self.layers):
            layer.self_attn.layer_idx = i
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.lm_head = ColumnParallelLinear(
            config.hidden_size, config.vocab_size, has_bias=False, gather_output=True)
        cos, sin = _rope_tables(config.head_dim, config.max_position_embeddings,
                                config.rope_theta)
        from ..core.tensor import Tensor

        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)

    def forward(self, input_ids, cache=None, positions=None):
        """Causal-LM forward; ``cache`` opts into KV-cached decode.

        cache:     None (full causal forward, unchanged) or a KV cache view
                   (``serving.DenseKVCache`` for concat-style past_kv,
                   ``serving.PagedCacheView`` inside the serving engine).
                   The cache absorbs each layer's new K/V and answers
                   attention over past + new — inference-only (no_grad).
        positions: int [B, S] token positions for RoPE when the inputs are
                   a suffix (cached decode); defaults to 0..S-1.
        """
        if cache is None:
            return self._forward_body(input_ids, None, positions)
        from ..core.autograd import no_grad

        with no_grad():
            return self._forward_body(input_ids, cache, positions)

    def _forward_body(self, input_ids, cache, positions):
        h = self.embed_tokens(input_ids)
        for layer in self.layers:
            h = layer(h, self.rope_cos, self.rope_sin, cache=cache,
                      positions=positions)
        h = self.norm(h)
        return self.lm_head(h)

    def cache_layers(self):
        """What each attention layer keeps in a KV cache (the serving
        engine sizes its pool from this): every layer alike, no window."""
        c = self.config
        return [CacheLayer(c.num_key_value_heads, c.head_dim)
                ] * c.num_hidden_layers
