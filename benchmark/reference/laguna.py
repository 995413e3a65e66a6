"""Plain reference of the Laguna decoder (poolside Laguna-XS.2): the forward
pass in straightforward ``jax.numpy`` and float32, under
``jax.default_matmul_precision("highest")``. No kernels, no cache, no
sorting or grouping of rows, and nothing imported from the program.

It follows the published ``config.json``; ``cfg`` is a configuration file's
dict. Layer ``l`` (hidden ``H``, head size ``D``, ``Hkv`` KV heads, ``eps`` =
``rms_norm_eps``):

- ``x = RMSNorm(h)``. ``n = num_attention_heads_per_layer[l]``. ``q = x Wq``
  ``[H -> n D]``, ``k, v = x Wk, x Wv`` ``[H -> Hkv D]``, no bias; query head
  ``j`` reads KV head ``j // (n / Hkv)``.
- RoPE by ``rope_parameters[layer_types[l]]``, rotate-half, over the first
  ``partial_rotary_factor D`` dims of each head (``d`` of them), the rest
  pass. ``rope_type`` "default": ``inv_freq_i = theta^(-2i/d)``. "yarn":
  ``inv_freq_i = interp_i (1 - r_i) + extrap_i r_i`` with ``extrap_i =
  theta^(-2i/d)``, ``interp_i = extrap_i / factor``, ``r_i = 1 - clip((i -
  low) / (high - low), 0, 1)``, ``low = floor(c(beta_fast))``, ``high =
  ceil(c(beta_slow))`` clipped to ``[0, d - 1]``, ``c(b) = d ln(orig / (2 pi
  b)) / (2 ln theta)`` with ``orig = original_max_position_embeddings``; cos
  and sin multiplied by ``attention_factor``.
- Causal softmax attention, scale ``1 / sqrt(D)``; in a "sliding_attention"
  layer the query at ``s`` sees key ``t`` iff ``s - sliding_window < t <= s``.
- ``g = sigmoid(x Wg)``, ``Wg [H -> n]``: head ``j``'s output is multiplied
  by ``g_j``. ``h = h + concat(heads) Wo``.
- ``x2 = RMSNorm(h)``. ``mlp_layer_types[l]`` "dense": ``down(silu(gate(x2))
  * up(x2))`` at ``intermediate_size``. "sparse": ``s = sigmoid(x2 Wr)``
  ``[H -> num_experts]``; the ``num_experts_per_tok`` largest ``s_e`` are
  kept; ``w_e = moe_routed_scaling_factor s_e / sum(kept s)``; ``y = sum
  w_e E_e(x2) + E_shared(x2)``, every expert the gated form at its width,
  the weights on the outputs. ``h = h + y``.

Final RMSNorm, untied head.

Departures and what the source leaves open (the configuration's ``assumed``
states them): the output gate is one sigmoid a query head (``"gating":
true`` says no more); the router scores by sigmoid and has no expert groups
and no selection bias; no q/k norm. q|k|v and gate|up are stored fused along
the output axis and experts stacked in front (the benchmark's weight layout,
split here).

To fit beside the bf16 leaves on the chip, attention runs one KV head's
group of query heads at a time (``lax.map``) and the sparse layer one
expert at a time (``lax.scan``, summing as it goes): every expert is applied
to every token and weighed by zero where the token did not choose it, so
nothing is sorted, gathered or dropped. ``linear`` is the one place a matrix is applied; the lower-precision
control passes ``int8_linear`` there.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def f32_linear(x, w):
    return jnp.matmul(x, w.astype(jnp.float32), precision="highest")


def _fake_int8(a, axis):
    """Symmetric int8 with one scale per row along ``axis`` (absmax / 127)."""
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(a / scale), -127, 127) * scale


def int8_linear(x, w):
    """The control: W8A8. Activations quantised per token, weights per output
    channel, products accumulated exactly (float32, highest)."""
    xq = _fake_int8(x, axis=-1)
    wq = _fake_int8(w.astype(jnp.float32), axis=0)
    return jnp.matmul(xq, wq, precision="highest")


def rms_norm(x, weight, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)


def inv_freq(d, p):
    """Inverse frequencies ``[d / 2]`` of one layer type's RoPE over ``d``
    rotated dims, and the factor its cos and sin are multiplied by."""
    theta = float(p["rope_theta"])
    i = jnp.arange(d // 2, dtype=jnp.float32)
    extrap = theta ** (-2.0 * i / d)
    if p.get("rope_type", "default") != "yarn":
        return extrap, 1.0
    orig = float(p["original_max_position_embeddings"])

    def c(beta):
        return d * math.log(orig / (2 * math.pi * beta)) / (2 * math.log(theta))

    low = max(math.floor(c(float(p["beta_fast"]))), 0)
    high = min(math.ceil(c(float(p["beta_slow"]))), d - 1)
    r = 1.0 - jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    interp = extrap / float(p["factor"])
    return interp * (1.0 - r) + extrap * r, float(p["attention_factor"])


def rope(x, p):
    """x: [B, S, heads, D] at positions 0..S-1."""
    d = int(x.shape[-1] * float(p.get("partial_rotary_factor", 1.0)))
    freq, factor = inv_freq(d, p)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq[None]
    cos = (jnp.cos(ang) * factor)[None, :, None, :]
    sin = (jnp.sin(ang) * factor)[None, :, None, :]
    x1, x2, rest = x[..., :d // 2], x[..., d // 2:d], x[..., d:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def attention(q, k, v, window):
    """Causal softmax attention, within the latest ``window`` positions if
    given. q: [B, S, n, D]; k, v: [B, S, Hkv, D]."""
    b, s, n, d = q.shape
    hkv = k.shape[2]
    rep = n // hkv
    qi = jnp.arange(s)[:, None]
    kj = jnp.arange(s)[None, :]
    mask = kj <= qi
    if window is not None:
        mask &= kj > qi - window

    def group(args):
        qg, kg, vg = args          # [B, S, rep, D], [B, S, D], [B, S, D]
        sc = jnp.einsum("bsrd,btd->brst", qg, kg, precision="highest")
        sc = jnp.where(mask, sc / jnp.sqrt(jnp.float32(d)), -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("brst,btd->bsrd", p, vg, precision="highest")

    qg = jnp.moveaxis(q.reshape(b, s, hkv, rep, d), 2, 0)
    out = jax.lax.map(group,
                      (qg, jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)))
    return jnp.moveaxis(out, 0, 2).reshape(b, s, n, d)


def gated_mlp(x, gate_up, down, linear):
    gate, up = jnp.split(linear(x, gate_up), 2, axis=-1)
    return linear(jax.nn.silu(gate) * up, down)


def sparse_mlp(cfg, lw, x, linear):
    """Every expert over every token, weighed by the router (zero where the
    token did not choose the expert), plus the shared expert."""
    e, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(linear(x, lw["mlp.router.weight"]))     # [B, S, E]
    top_s, top_e = jax.lax.top_k(s, k)
    w = cfg["moe_routed_scaling_factor"] * top_s / jnp.sum(
        top_s, axis=-1, keepdims=True)
    weight = jnp.sum(jax.nn.one_hot(top_e, e, dtype=jnp.float32)
                     * w[..., None], axis=-2)                  # [B, S, E]

    def add_expert(y, args):
        gate_up, down, w_e = args
        return y + w_e[..., None] * gated_mlp(x, gate_up, down, linear), None

    routed, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(x),
        (lw["mlp.gate_up_proj"], lw["mlp.down_proj"],
         jnp.moveaxis(weight, -1, 0)))
    return routed + gated_mlp(
        x, lw["mlp.shared_expert.gate_up_proj.weight"],
        lw["mlp.shared_expert.down_proj.weight"], linear)


def decoder_layer(cfg, layer, lw, h, linear):
    n = cfg["num_attention_heads_per_layer"][layer]
    hkv, d = cfg["num_key_value_heads"], cfg["head_dim"]
    kind = cfg["layer_types"][layer]
    b, s, _ = h.shape
    x = rms_norm(h, lw["input_layernorm.weight"], cfg["rms_norm_eps"])
    qkv = linear(x, lw["self_attn.qkv_proj.weight"])
    q, k, v = jnp.split(qkv, [n * d, (n + hkv) * d], axis=-1)
    p = cfg["rope_parameters"][kind]
    q = rope(q.reshape(b, s, n, d), p)
    k = rope(k.reshape(b, s, hkv, d), p)
    a = attention(q, k, v.reshape(b, s, hkv, d),
                  cfg["sliding_window"] if kind == "sliding_attention"
                  else None)
    g = jax.nn.sigmoid(linear(x, lw["self_attn.gate_proj.weight"]))
    a = (a * g[..., None]).reshape(b, s, n * d)
    h = h + linear(a, lw["self_attn.o_proj.weight"])
    x2 = rms_norm(h, lw["post_attention_layernorm.weight"],
                  cfg["rms_norm_eps"])
    if cfg["mlp_layer_types"][layer] == "dense":
        return h + gated_mlp(x2, lw["mlp.gate_up_proj.weight"],
                             lw["mlp.down_proj.weight"], linear)
    return h + sparse_mlp(cfg, lw, x2, linear)


def hidden_states(cfg, w, tokens, linear=f32_linear):
    """Final-norm output [B, S, H] for int tokens [B, S]."""
    h = w["embed_tokens.weight"][tokens].astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        p = f"layers.{i}."
        lw = {n[len(p):]: a for n, a in w.items() if n.startswith(p)}
        h = decoder_layer(cfg, i, lw, h, linear)
    return rms_norm(h, w["norm.weight"], cfg["rms_norm_eps"])


def logits(cfg, w, tokens, linear=f32_linear):
    """[B, S, V] float32 logits of a full causal forward pass."""
    with jax.default_matmul_precision("highest"):
        return linear(hidden_states(cfg, w, tokens, linear),
                      w["lm_head.weight"])
