"""Falcon-H1 family (tiiuae Falcon-H1-34B-Instruct): a decoder whose every
block runs two token mixers side by side on one normed input and adds them,
a Mamba-2 (SSD) mixer and GQA attention, and follows them with a gated MLP;
fixed scalar multipliers (muP) sit on the embedding, the head, the keys,
both mixers' inputs and outputs, five sections of the mixer's input
projection and two places in the MLP.

Built from ``models/llama.py``'s parts where the multipliers allow
(``RMSNorm``, the fused ``qkv_proj`` / ``gate_up_proj`` layers, the RoPE
tables and helpers). Served by ``LLMEngine`` like any cache-aware model:
``forward(ids, cache=, positions=)`` and ``cache_layers()``, which says what
each block keeps: a ``CacheLayer`` (its K/V, paged) and a ``StateLayer``
(the mixer's recurrent state and its conv's last inputs, one row a running
sequence, whatever its length). The cache view's hooks: ``attend`` for the
attention, ``shift`` and ``recur`` for the mixer, ``last_rows`` for the
head (a prefill samples one position: no ``[P, vocab]`` logits).

Block (``rms(x, w) = x / sqrt(mean(x^2) + eps) w``):

- ``u = rms(h, w_in)``; ``h += ssm_out_multiplier Mixer(u) +
  attention_out_multiplier Attn(attention_in_multiplier u)``; ``v = rms(h,
  w_ff)``; ``h += mlp_multipliers[1] ((v W_up) silu(mlp_multipliers[0] (v
  W_gate))) W_down``.
- Attn: ``q = a W_q``, ``k = (a W_k) key_multiplier``, ``v = a W_v``, no
  bias; rotate-half RoPE over all of ``head_dim`` (the config's own, not
  ``hidden / heads``); causal softmax attention, scale ``head_dim^-1/2``.
- Mixer (``d = mamba_d_ssm``, ``H`` heads of ``P``, ``G`` groups, state
  ``N``, conv ``K``): ``p = ((ssm_in_multiplier u) W_in) m``, ``m`` the
  five ``ssm_multipliers`` on the columns z | x | B | C | dt; ``c = silu(conv
  (x|B|C) + b)`` depthwise and causal; ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``; ``S_t = exp(dt_t A) S_(t-1) + dt_t x_t (x) B_t``,
  ``y_t = S_t C_t + D x_t``; ``g = y silu(z)`` RMS-normalised within each
  group of ``d / G`` channels, times ``w_norm``; then ``W_out``.

The multipliers are compile-time constants applied where the equations put
them, in float32 with one rounding to the model's dtype (none is folded
into a weight: that would change the rounding). The recurrence runs in
``kernels/ssd_chunk_scan.py`` (a whole sequence) and
``kernels/ssm_state_update.py`` (one token a slot, the state in place); the
state is float32.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass, field

import numpy as np

import jax
import jax.numpy as jnp

from .. import nn
from ..core.dispatch import apply as _apply
from ..distributed.mp_layers import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
)
from ..kernels.ssd_chunk_scan import ssd_chunk_scan
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.functional.attention import CacheLayer, StateLayer
from ..ops import manipulation as M
from .llama import LlamaMLP, _rope_tables, apply_rope_at

__all__ = ["FalconH1Config", "FalconH1ForCausalLM", "falcon_h1_tiny"]


@dataclass
class FalconH1Config:
    vocab_size: int = 261120
    hidden_size: int = 5120
    intermediate_size: int = 21504
    num_hidden_layers: int = 72
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e11
    tie_word_embeddings: bool = False
    mamba_d_ssm: int = 4096
    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_d_state: int = 256
    mamba_n_groups: int = 2
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    key_multiplier: float = 0.011048543456039804
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    ssm_multipliers: list = field(default_factory=lambda: [
        0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
        0.3535533905932738])
    mlp_multipliers: list = field(default_factory=lambda: [
        0.1767766952966369, 0.011160714285714284])

    def __post_init__(self):
        if self.mamba_n_heads * self.mamba_d_head != self.mamba_d_ssm:
            raise ValueError(
                f"mamba_d_ssm {self.mamba_d_ssm} is not mamba_n_heads "
                f"{self.mamba_n_heads} x mamba_d_head {self.mamba_d_head}")

    @classmethod
    def from_dict(cls, doc):
        """From a ``config.json``'s dict; keys this model does not read
        (the flags the published model leaves at the values the equations
        above assume) are passed over."""
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in doc.items() if k in names})

    @property
    def conv_dim(self):
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state


def falcon_h1_tiny(vocab=256, hidden=64, layers=2, heads=10, kv_heads=2,
                   head_dim=8, inter=128, mamba_heads=4, mamba_head_dim=8,
                   state=8, groups=2, chunk=8, seq=128):
    """A small Falcon-H1 for CPU tests: both mixers in every block, five
    query heads a KV head, two B/C groups, the published multipliers."""
    return FalconH1Config(
        vocab_size=vocab, hidden_size=hidden, intermediate_size=inter,
        num_hidden_layers=layers, num_attention_heads=heads,
        num_key_value_heads=kv_heads, head_dim=head_dim,
        max_position_embeddings=seq, mamba_d_ssm=mamba_heads * mamba_head_dim,
        mamba_n_heads=mamba_heads, mamba_d_head=mamba_head_dim,
        mamba_d_state=state, mamba_n_groups=groups, mamba_chunk_size=chunk)


def _scale(x, m):
    """``x m`` for a compile-time ``m``: in float32, rounded once."""
    if m == 1:
        return x
    return (x.astype(jnp.float32) * jnp.float32(m)).astype(x.dtype)


def _scaled(t, m):
    return _apply(functools.partial(_scale, m=m), t, op_name="scale")


class FalconH1Attention(nn.Layer):
    def __init__(self, config: FalconH1Config, layer_idx: int):
        super().__init__()
        c = self.config = config
        self.layer_idx = layer_idx
        self.num_heads = c.num_attention_heads
        self.num_kv_heads = c.num_key_value_heads
        self.head_dim = c.head_dim
        qkv_out = (self.num_heads + 2 * self.num_kv_heads) * self.head_dim
        self.qkv_proj = ColumnParallelLinear(
            c.hidden_size, qkv_out, has_bias=False, gather_output=False)
        self.o_proj = RowParallelLinear(
            self.num_heads * self.head_dim, c.hidden_size, has_bias=False,
            input_is_parallel=True)

    def forward(self, x, rope_cos, rope_sin, cache=None, positions=None):
        B, S = x.shape[0], x.shape[1]
        q_sz = self.num_heads * self.head_dim
        kv_sz = self.num_kv_heads * self.head_dim
        q, k, v = M.split(self.qkv_proj(x), [q_sz, kv_sz, kv_sz], axis=-1)
        # the keys' multiplier sits between the projection and RoPE
        k = _scaled(k, self.config.key_multiplier)
        q = M.reshape(q, [B, S, self.num_heads, self.head_dim])
        k = M.reshape(k, [B, S, self.num_kv_heads, self.head_dim])
        v = M.reshape(v, [B, S, self.num_kv_heads, self.head_dim])
        if positions is None:
            positions = jnp.arange(S, dtype=jnp.int32)[None]
        q = _apply(apply_rope_at, q, rope_cos, rope_sin, positions,
                   op_name="rope")
        k = _apply(apply_rope_at, k, rope_cos, rope_sin, positions,
                   op_name="rope")
        if cache is None:
            out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        else:
            out = _apply(functools.partial(cache.attend, self.layer_idx),
                         q, k, v, op_name="kv_cached_attention")
        return self.o_proj(M.reshape(out, [B, S, q_sz]))


class _DepthwiseConv(nn.Layer):
    """The parameters of a causal depthwise conv: ``weight [C, K]`` (tap
    ``k`` multiplies the input ``K - 1 - k`` positions back), ``bias [C]``."""

    def __init__(self, channels, width):
        super().__init__()
        self.weight = self.create_parameter([channels, width])
        self.bias = self.create_parameter([channels], is_bias=True)


class _Weight(nn.Layer):
    def __init__(self, size):
        super().__init__()
        self.weight = self.create_parameter(
            [size], default_initializer=I.Constant(1.0))


class FalconH1Mixer(nn.Layer):
    """The Mamba-2 mixer. ``layer_idx`` counts the model's state layers."""

    def __init__(self, config: FalconH1Config, layer_idx: int):
        super().__init__()
        c = self.config = config
        self.layer_idx = layer_idx
        heads = c.mamba_n_heads
        self.in_proj = ColumnParallelLinear(
            c.hidden_size, c.mamba_d_ssm + c.conv_dim + heads,
            has_bias=False, gather_output=True)
        self.conv1d = _DepthwiseConv(c.conv_dim, c.mamba_d_conv)
        one = I.Constant(1.0)
        self.A_log = self.create_parameter(
            [heads], default_initializer=I.Assign(
                np.log(np.arange(1, heads + 1, dtype=np.float32))))
        self.D = self.create_parameter([heads], default_initializer=one)
        self.dt_bias = self.create_parameter([heads], default_initializer=one)
        self.norm = _Weight(c.mamba_d_ssm)
        self.out_proj = RowParallelLinear(
            c.mamba_d_ssm, c.hidden_size, has_bias=False,
            input_is_parallel=False)
        d, gn = c.mamba_d_ssm, c.mamba_n_groups * c.mamba_d_state
        m = c.ssm_multipliers
        self._mup = np.concatenate([
            np.full(d, m[0]), np.full(d, m[1]), np.full(gn, m[2]),
            np.full(gn, m[3]), np.full(heads, m[4])]).astype(np.float32)

    def forward(self, u, cache=None):
        proj = self.in_proj(_scaled(u, self.config.ssm_in_multiplier))
        y = _apply(functools.partial(self._mix, cache), proj,
                   self.conv1d.weight, self.conv1d.bias, self.A_log, self.D,
                   self.dt_bias, self.norm.weight, op_name="mamba2_mixer")
        return self.out_proj(y)

    def _mix(self, cache, proj, conv_w, conv_b, a_log, d_skip, dt_bias,
             norm_w):
        """Raw arrays: from the input projection ``[B, S, 2 d + 2 G N +
        H]`` to the gated, normed scan output ``[B, S, d]``."""
        c = self.config
        d, heads, p = c.mamba_d_ssm, c.mamba_n_heads, c.mamba_d_head
        g, n, k = c.mamba_n_groups, c.mamba_d_state, c.mamba_d_conv
        dtype = proj.dtype
        f32 = jnp.float32
        b, s, _ = proj.shape
        proj = (proj.astype(f32) * self._mup).astype(dtype)
        z, xbc, dt_raw = jnp.split(proj, [d, d + c.conv_dim], axis=-1)
        # the K - 1 inputs before this step's in front (zeros before a
        # sequence; a serving cache keeps a running sequence's)
        window = (jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0))) if cache is None
                  else cache.shift(self.layer_idx, xbc))
        conv = self._conv(window, conv_w, conv_b).astype(dtype)
        x, bmat, cmat = jnp.split(conv, [d, d + g * n], axis=-1)
        x = x.reshape(b, s, heads, p)
        bmat, cmat = bmat.reshape(b, s, g, n), cmat.reshape(b, s, g, n)
        dt = jax.nn.softplus(dt_raw.astype(f32) + dt_bias.astype(f32))
        a = -jnp.exp(a_log.astype(f32))
        if cache is None:
            y = jax.vmap(lambda *seq: ssd_chunk_scan(
                *seq[:2], a, *seq[2:], chunk=c.mamba_chunk_size)[0])(
                x, dt, bmat, cmat)
        else:
            y = cache.recur(self.layer_idx, x, dt, a, bmat, cmat,
                            chunk=c.mamba_chunk_size)
        y = y + d_skip.astype(f32)[:, None] * x.astype(f32)
        gated = self._gate(y.reshape(b, s, d), z)
        return (self._group_norm(gated) * norm_w.astype(f32)).astype(dtype)

    @staticmethod
    def _conv(window, conv_w, conv_b):
        """``silu(conv + bias)`` in float32 of ``window [B, S + K - 1, C]``
        (the ``K - 1`` inputs before the step's in front): ``[B, S, C]``."""
        f32 = jnp.float32
        k = conv_w.shape[1]
        s = window.shape[1] - (k - 1)
        w = conv_w.astype(f32)
        conv = sum(window[:, i:i + s].astype(f32) * w[:, i] for i in range(k))
        return jax.nn.silu(conv + conv_b.astype(f32))

    @staticmethod
    def _gate(y, z):
        return y * jax.nn.silu(z.astype(jnp.float32))

    def _group_norm(self, gated):
        """RMS-normalise float32 ``[B, S, d]`` within each of the ``G``
        groups of ``d / G`` channels."""
        c = self.config
        grouped = gated.reshape(*gated.shape[:2], c.mamba_n_groups, -1)
        var = jnp.mean(jnp.square(grouped), axis=-1, keepdims=True)
        return (grouped * jax.lax.rsqrt(var + c.rms_norm_eps)).reshape(
            gated.shape)


class FalconH1MLP(LlamaMLP):
    """``LlamaMLP``'s fused layers, the two multipliers where the source
    puts them: on the gate before the activation, on the output."""

    def __init__(self, config: FalconH1Config):
        super().__init__(config)
        self.gate_multiplier, self.down_multiplier = config.mlp_multipliers

    def forward(self, x):
        gate, up = M.split(self.gate_up_proj(x), 2, axis=-1)
        y = self.down_proj(up * F.silu(_scaled(gate, self.gate_multiplier)))
        return _scaled(y, self.down_multiplier)


class FalconH1DecoderLayer(nn.Layer):
    def __init__(self, config: FalconH1Config, layer_idx: int):
        super().__init__()
        c = self.config = config
        self.input_layernorm = nn.RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.self_attn = FalconH1Attention(c, layer_idx)
        self.mamba = FalconH1Mixer(c, layer_idx)
        self.pre_ff_layernorm = nn.RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.feed_forward = FalconH1MLP(c)

    def forward(self, h, rope_cos, rope_sin, cache=None, positions=None):
        c = self.config
        u = self.input_layernorm(h)
        mixed = _scaled(self.mamba(u, cache=cache), c.ssm_out_multiplier)
        attended = _scaled(
            self.self_attn(_scaled(u, c.attention_in_multiplier), rope_cos,
                           rope_sin, cache=cache, positions=positions),
            c.attention_out_multiplier)
        h = h + (mixed + attended)
        return h + self.feed_forward(self.pre_ff_layernorm(h))


class FalconH1ForCausalLM(nn.Layer):
    def __init__(self, config: FalconH1Config):
        super().__init__()
        from ..core.tensor import Tensor

        c = self.config = config
        self.embed_tokens = VocabParallelEmbedding(c.vocab_size, c.hidden_size)
        self.layers = nn.LayerList(
            [FalconH1DecoderLayer(c, i) for i in range(c.num_hidden_layers)])
        self.final_layernorm = nn.RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.lm_head = ColumnParallelLinear(
            c.hidden_size, c.vocab_size, has_bias=False, gather_output=True)
        cos, sin = _rope_tables(c.head_dim, c.max_position_embeddings,
                                c.rope_theta)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)

    def cache_layers(self):
        """What each block keeps for a running sequence, in the order its
        layers count themselves: the attention's K/V (paged, no window) and
        the mixer's state (float32 ``[H, N, P]``, a head's channels along
        the last axis) with its conv's last ``K - 1`` inputs."""
        c = self.config
        kv = CacheLayer(c.num_key_value_heads, c.head_dim)
        state = StateLayer(
            ((c.mamba_n_heads, c.mamba_d_state, c.mamba_d_head), "float32"),
            ((c.mamba_d_conv - 1, c.conv_dim), None))
        return [kv, state] * c.num_hidden_layers

    def forward(self, input_ids, cache=None, positions=None):
        """Causal-LM forward; ``cache`` / ``positions`` as in
        ``LlamaForCausalLM.forward`` (inference-only with a cache). With a
        serving cache the logits are those of the rows the step samples
        (``cache.last_rows``): a prefill returns ``[1, 1, vocab]``."""
        if cache is None:
            return self._forward_body(input_ids, None, positions)
        from ..core.autograd import no_grad

        with no_grad():
            return self._forward_body(input_ids, cache, positions)

    def _forward_body(self, input_ids, cache, positions):
        c = self.config
        h = _scaled(self.embed_tokens(input_ids), c.embedding_multiplier)
        for layer in self.layers:
            h = layer(h, self.rope_cos, self.rope_sin, cache=cache,
                      positions=positions)
        if cache is not None:
            h = _apply(cache.last_rows, h, op_name="last_rows")
        return _scaled(self.lm_head(self.final_layernorm(h)),
                       c.lm_head_multiplier)

    def num_params(self):
        return sum(p.size for p in self.parameters())
