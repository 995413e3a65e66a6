"""Laguna family (poolside Laguna-XS.2): a decoder that mixes full and
sliding-window attention layers with per-layer query head counts, gates each
attention head's output, and follows a leading dense MLP layer with sparse
expert layers (top-k of many small experts plus a shared expert).

Built from ``models/llama.py``'s parts where they fit (``RMSNorm``, the
fused ``qkv_proj`` / ``gate_up_proj`` layers, the RoPE helpers); the sparse
layers are ``distributed.moe.SparseMoELayer``. Served by ``LLMEngine`` like
any cache-aware model: ``forward(ids, cache=, positions=)`` and
``cache_layers()``, which says each layer's KV heads, head size and window.

Layer ``l`` (hidden ``H``, head size ``D``, ``Hkv`` KV heads):

- ``x = RMSNorm(h)``; ``n = num_attention_heads_per_layer[l]`` query heads;
  q, k, v without bias; query head ``j`` reads KV head ``j // (n / Hkv)``.
- RoPE by the layer's type (``rope_parameters[layer_types[l]]``),
  rotate-half over the first ``partial_rotary_factor * D`` dims of a head,
  the rest pass. ``rope_type`` "default": ``inv_freq = theta^(-2i/d)``.
  "yarn": per frequency ``inv_freq = interp (1 - r) + extrap r`` with
  ``extrap = theta^(-2i/d)``, ``interp = extrap / factor`` and ``r`` one
  below the correction range of ``beta_fast``, zero above that of
  ``beta_slow``, linear between; cos and sin times ``attention_factor``.
- causal softmax attention, scale ``1/sqrt(D)``; a "sliding_attention"
  layer's query at ``s`` sees keys ``s - sliding_window < t <= s``.
- ``g = sigmoid(x Wg)``, one gate a query head; head ``j``'s output times
  ``g_j``; ``h = h + concat(heads) Wo``.
- ``x2 = RMSNorm(h)``; "dense": ``down(silu(gate(x2)) * up(x2))`` at
  ``intermediate_size``; "sparse": sigmoid router scores over
  ``num_experts``, the ``num_experts_per_tok`` largest kept, weights
  ``moe_routed_scaling_factor * s_e / sum(kept s)`` on the experts'
  outputs, plus the shared expert. ``h = h + mlp(x2)``.

Final RMSNorm, untied head.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field
from types import SimpleNamespace

import numpy as np

import jax.numpy as jnp

from .. import nn
from ..core.dispatch import apply as _apply
from ..distributed.moe import SparseMoELayer
from ..distributed.mp_layers import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
)
from ..nn import functional as F
from ..nn.functional.attention import CacheLayer, sdpa_window_ref
from ..ops import manipulation as M
from .llama import LlamaMLP, apply_rope_at

__all__ = ["LagunaConfig", "LagunaForCausalLM", "laguna_tiny",
           "laguna_rope_tables"]

_PERIOD = ("full_attention", "sliding_attention", "sliding_attention",
           "sliding_attention")


def _default_rope():
    return {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000.0, "factor": 64.0,
            "original_max_position_embeddings": 4096, "beta_fast": 64.0,
            "beta_slow": 1.0, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {
            "rope_type": "default", "rope_theta": 10000.0,
            "partial_rotary_factor": 1.0},
    }


@dataclass
class LagunaConfig:
    """Laguna-XS.2's published values by default (40 layers)."""

    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192
    num_hidden_layers: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    sliding_window: int = 512
    num_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    moe_routed_scaling_factor: float = 2.5
    # (first, count) of the experts this chip holds; None: all of them
    experts_held: tuple | None = None
    # per layer; None: the published pattern at ``num_hidden_layers``
    layer_types: list | None = None
    mlp_layer_types: list | None = None
    num_attention_heads_per_layer: list | None = None
    rope_parameters: dict = field(default_factory=_default_rope)

    def __post_init__(self):
        n = self.num_hidden_layers
        if self.layer_types is None:
            self.layer_types = [_PERIOD[i % 4] for i in range(n)]
        if self.mlp_layer_types is None:
            self.mlp_layer_types = ["dense"] + ["sparse"] * (n - 1)
        if self.num_attention_heads_per_layer is None:
            self.num_attention_heads_per_layer = [
                48 if t == "full_attention" else 64 for t in self.layer_types]
        for name in ("layer_types", "mlp_layer_types",
                     "num_attention_heads_per_layer"):
            if len(getattr(self, name)) != n:
                raise ValueError(f"{name} has {len(getattr(self, name))} "
                                 f"entries for {n} layers")


def laguna_tiny(vocab=256, hidden=64, layers=5, kv_heads=2, head_dim=16,
                inter=128, experts=8, top_k=2, expert_inter=32, window=8,
                seq=128, experts_held=None):
    """A small Laguna for CPU tests: the published layer pattern, 6 and 8
    query heads a KV head, a window shorter than the contexts."""
    types = [_PERIOD[i % 4] for i in range(layers)]
    return LagunaConfig(
        vocab_size=vocab, hidden_size=hidden, intermediate_size=inter,
        num_hidden_layers=layers, num_key_value_heads=kv_heads,
        head_dim=head_dim, max_position_embeddings=seq, sliding_window=window,
        num_experts=experts, num_experts_per_tok=top_k,
        moe_intermediate_size=expert_inter,
        shared_expert_intermediate_size=expert_inter,
        experts_held=experts_held, layer_types=types,
        num_attention_heads_per_layer=[
            kv_heads * (6 if t == "full_attention" else 8) for t in types])


def _yarn_inv_freq(rot_dim, p):
    """YaRN's per-frequency blend of interpolated and original inverse
    frequencies (float64 numpy)."""
    theta, factor = float(p["rope_theta"]), float(p["factor"])
    orig = float(p["original_max_position_embeddings"])
    extrap = 1.0 / theta ** (np.arange(0, rot_dim, 2) / rot_dim)
    interp = extrap / factor

    def correction_dim(rotations):
        return (rot_dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(float(p["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(p["beta_slow"]))), rot_dim - 1)
    ramp = np.clip((np.arange(rot_dim // 2) - low) / max(high - low, 1e-3),
                   0.0, 1.0)
    r = 1.0 - ramp
    return interp * (1.0 - r) + extrap * r


def laguna_rope_tables(head_dim, max_seq, p, dtype=jnp.float32):
    """cos and sin ``[max_seq, rot_dim / 2]`` of one layer type's RoPE
    (``rope_parameters[type]``), scaled by its ``attention_factor``."""
    rot_dim = int(head_dim * float(p.get("partial_rotary_factor", 1.0)))
    if p.get("rope_type", "default") == "yarn":
        inv_freq = _yarn_inv_freq(rot_dim, p)
        scale = float(p.get("attention_factor") or 1.0)
    else:
        inv_freq = 1.0 / float(p["rope_theta"]) ** (
            np.arange(0, rot_dim, 2) / rot_dim)
        scale = 1.0
    freqs = np.outer(np.arange(max_seq), inv_freq)
    return (jnp.asarray(np.cos(freqs) * scale, dtype),
            jnp.asarray(np.sin(freqs) * scale, dtype))


def _rope_partial(x, cos, sin, positions):
    """Rotate-half RoPE over the first ``2 * cos.shape[-1]`` dims of each
    head of x [B, S, H, D] at ``positions`` [B, S]; the rest pass."""
    rot = 2 * cos.shape[-1]
    out = apply_rope_at(x[..., :rot], cos, sin, positions)
    if rot == x.shape[-1]:
        return out
    return jnp.concatenate([out, x[..., rot:]], axis=-1)


class LagunaAttention(nn.Layer):
    def __init__(self, config: LagunaConfig, layer_idx: int):
        super().__init__()
        c = config
        self.layer_idx = layer_idx
        self.layer_type = c.layer_types[layer_idx]
        self.num_heads = c.num_attention_heads_per_layer[layer_idx]
        self.num_kv_heads = c.num_key_value_heads
        self.head_dim = c.head_dim
        self.window = (c.sliding_window
                       if self.layer_type == "sliding_attention" else None)
        qkv_out = (self.num_heads + 2 * self.num_kv_heads) * self.head_dim
        self.qkv_proj = ColumnParallelLinear(
            c.hidden_size, qkv_out, has_bias=False, gather_output=False)
        self.gate_proj = ColumnParallelLinear(
            c.hidden_size, self.num_heads, has_bias=False,
            gather_output=False)
        self.o_proj = RowParallelLinear(
            self.num_heads * self.head_dim, c.hidden_size, has_bias=False,
            input_is_parallel=True)

    def forward(self, x, rope, cache=None, positions=None):
        B, S = x.shape[0], x.shape[1]
        q_sz = self.num_heads * self.head_dim
        kv_sz = self.num_kv_heads * self.head_dim
        q, k, v = M.split(self.qkv_proj(x), [q_sz, kv_sz, kv_sz], axis=-1)
        q = M.reshape(q, [B, S, self.num_heads, self.head_dim])
        k = M.reshape(k, [B, S, self.num_kv_heads, self.head_dim])
        v = M.reshape(v, [B, S, self.num_kv_heads, self.head_dim])
        cos, sin = rope[self.layer_type]
        if positions is None:
            positions = jnp.arange(S, dtype=jnp.int32)[None]
        q = _apply(_rope_partial, q, cos, sin, positions, op_name="rope")
        k = _apply(_rope_partial, k, cos, sin, positions, op_name="rope")
        if cache is None:
            out = _apply(functools.partial(sdpa_window_ref,
                                           window=self.window),
                         q, k, v, op_name="sdpa")
        else:
            # the cache absorbs this layer's K/V and answers attention over
            # the context; it knows the layer's window from cache_layers()
            out = _apply(functools.partial(cache.attend, self.layer_idx),
                         q, k, v, op_name="kv_cached_attention")
        gate = M.reshape(F.sigmoid(self.gate_proj(x)),
                         [B, S, self.num_heads, 1])
        out = M.reshape(out * gate, [B, S, q_sz])
        return self.o_proj(out)


def _gated_mlp(hidden, inter):
    return LlamaMLP(SimpleNamespace(hidden_size=hidden,
                                    intermediate_size=inter))


class LagunaSparseMLP(SparseMoELayer):
    """The sparse layer in a decoder: ``[B, S, H] -> [B, S, H]``, and a
    serving step's cache view is told what was routed where and which path
    the experts' gated MLP took."""

    def __init__(self, config: LagunaConfig):
        c = config
        super().__init__(
            c.hidden_size, c.moe_intermediate_size, c.num_experts,
            c.num_experts_per_tok, experts_held=c.experts_held,
            shared_expert=_gated_mlp(c.hidden_size,
                                     c.shared_expert_intermediate_size),
            routed_scaling=c.moe_routed_scaling_factor)

    def forward(self, x, cache=None):
        if cache is None or not hasattr(cache, "count"):
            return super().forward(x)[0]
        from ..core.tensor import Tensor

        out, load = super().forward(
            x, row_mask=Tensor._wrap(cache.live_rows(tuple(x.shape[:2]))))
        cache.moe_paths["fused" if self.fused else "plain"] += 1
        load = load._value.astype(jnp.float32)
        pairs, held = jnp.sum(load), load.shape[0]
        cache.count(**{
            "moe.layers": 1.0,
            "moe.routed_pairs": pairs,
            "moe.experts_touched_share": jnp.sum(load > 0) / held,
            "moe.expert_load_max_over_mean":
                jnp.max(load) * held / jnp.maximum(pairs, 1.0)})
        return out


class LagunaDecoderLayer(nn.Layer):
    def __init__(self, config: LagunaConfig, layer_idx: int):
        super().__init__()
        c = config
        self.input_layernorm = nn.RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.self_attn = LagunaAttention(c, layer_idx)
        self.post_attention_layernorm = nn.RMSNorm(c.hidden_size,
                                                   c.rms_norm_eps)
        self.sparse = c.mlp_layer_types[layer_idx] == "sparse"
        self.mlp = (LagunaSparseMLP(c) if self.sparse
                    else _gated_mlp(c.hidden_size, c.intermediate_size))

    def forward(self, x, rope, cache=None, positions=None):
        h = x + self.self_attn(self.input_layernorm(x), rope, cache=cache,
                               positions=positions)
        x2 = self.post_attention_layernorm(h)
        return h + (self.mlp(x2, cache=cache) if self.sparse
                    else self.mlp(x2))


class LagunaForCausalLM(nn.Layer):
    def __init__(self, config: LagunaConfig):
        super().__init__()
        from ..core.tensor import Tensor

        c = self.config = config
        self.embed_tokens = VocabParallelEmbedding(c.vocab_size, c.hidden_size)
        self.layers = nn.LayerList(
            [LagunaDecoderLayer(c, i) for i in range(c.num_hidden_layers)])
        self.norm = nn.RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.lm_head = ColumnParallelLinear(
            c.hidden_size, c.vocab_size, has_bias=False, gather_output=True)
        # one RoPE table a layer type
        self._rope_types = sorted(set(c.layer_types))
        for t in self._rope_types:
            cos, sin = laguna_rope_tables(
                c.head_dim, c.max_position_embeddings, c.rope_parameters[t])
            self.register_buffer(f"rope_cos_{t}", Tensor(cos),
                                 persistable=False)
            self.register_buffer(f"rope_sin_{t}", Tensor(sin),
                                 persistable=False)

    def cache_layers(self):
        """What each attention layer keeps in a KV cache: the same KV width
        everywhere, a window on the sliding layers."""
        c = self.config
        return [CacheLayer(c.num_key_value_heads, c.head_dim,
                           layer.self_attn.window) for layer in self.layers]

    def forward(self, input_ids, cache=None, positions=None):
        """Causal-LM forward; ``cache`` / ``positions`` as in
        ``LlamaForCausalLM.forward`` (inference-only with a cache)."""
        if cache is None:
            return self._forward_body(input_ids, None, positions)
        from ..core.autograd import no_grad

        with no_grad():
            return self._forward_body(input_ids, cache, positions)

    def _forward_body(self, input_ids, cache, positions):
        rope = {t: (getattr(self, f"rope_cos_{t}"),
                    getattr(self, f"rope_sin_{t}"))
                for t in self._rope_types}
        h = self.embed_tokens(input_ids)
        for layer in self.layers:
            h = layer(h, rope, cache=cache, positions=positions)
        return self.lm_head(self.norm(h))

    def num_params(self):
        return sum(p.size for p in self.parameters())
