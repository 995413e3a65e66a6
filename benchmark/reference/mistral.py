"""Plain reference of the Mistral-7B decoder: forward pass, loss, gradients
and AdamW in straightforward ``jax.numpy`` and float32, under
``jax.default_matmul_precision("highest")``. No kernels, no cache, no
batching tricks, and nothing imported from the program.

It follows the published architecture (``MistralForCausalLM``): token
embedding; per layer RMSNorm, q/k/v projections without bias, rotate-half
RoPE at ``rope_theta``, grouped-query causal softmax attention (query head h
reads KV head ``h // (Hq / Hkv)``), output projection, residual, RMSNorm,
gated MLP ``down(silu(gate) * up)``, residual; final RMSNorm; untied head.
Departures: q|k|v and gate|up are stored fused along the output axis (the
benchmark's weight layout, split here); no sliding window (v0.3 has none).

To fit beside nothing else on the chip, attention runs one KV head's group of
query heads at a time, and the callers feed it rows in blocks.

``linear`` is the one place a matrix is applied; the lower-precision control
passes ``int8_linear`` there.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def f32_linear(x, w):
    return jnp.matmul(x, w.astype(jnp.float32), precision="highest")


def _fake_int8(a, axis):
    """Symmetric int8 with one scale per row along ``axis`` (absmax / 127),
    straight-through for gradients."""
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(a / scale), -127, 127) * scale
    return a + jax.lax.stop_gradient(q - a)


def int8_linear(x, w):
    """The control: W8A8. Activations quantised per token, weights per output
    channel, products accumulated exactly (float32, highest)."""
    xq = _fake_int8(x, axis=-1)
    wq = _fake_int8(w.astype(jnp.float32), axis=0)
    return jnp.matmul(xq, wq, precision="highest")


def rms_norm(x, weight, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)


def rope(x, theta):
    """x: [B, S, H, D] at positions 0..S-1, rotate-half convention."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq[None]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v):
    """Causal softmax attention. q: [B, S, Hq, D]; k, v: [B, S, Hkv, D]."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    rep = hq // hkv
    mask = jnp.tril(jnp.ones((s, s), bool))

    def group(args):
        qg, kg, vg = args          # [B, S, rep, D], [B, S, D], [B, S, D]
        sc = jnp.einsum("bsrd,btd->brst", qg, kg, precision="highest")
        sc = jnp.where(mask, sc / jnp.sqrt(jnp.float32(d)), -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("brst,btd->bsrd", p, vg, precision="highest")

    qg = jnp.moveaxis(q.reshape(b, s, hkv, rep, d), 2, 0)
    out = jax.lax.map(jax.checkpoint(group),
                      (qg, jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)))
    return jnp.moveaxis(out, 0, 2).reshape(b, s, hq * d)


def decoder_layer(cfg, lw, h, linear):
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or cfg["hidden_size"] // hq
    b, s, _ = h.shape
    x = rms_norm(h, lw["input_layernorm.weight"], cfg["rms_norm_eps"])
    qkv = linear(x, lw["self_attn.qkv_proj.weight"])
    q, k, v = jnp.split(qkv, [hq * d, (hq + hkv) * d], axis=-1)
    q = rope(q.reshape(b, s, hq, d), cfg["rope_theta"])
    k = rope(k.reshape(b, s, hkv, d), cfg["rope_theta"])
    a = attention(q, k, v.reshape(b, s, hkv, d))
    h = h + linear(a, lw["self_attn.o_proj.weight"])
    x = rms_norm(h, lw["post_attention_layernorm.weight"], cfg["rms_norm_eps"])
    gate, up = jnp.split(linear(x, lw["mlp.gate_up_proj.weight"]), 2, axis=-1)
    return h + linear(jax.nn.silu(gate) * up, lw["mlp.down_proj.weight"])


def hidden_states(cfg, w, tokens, linear=f32_linear, remat=False):
    """Final-norm output [B, S, H] for int tokens [B, S]."""
    h = w["embed_tokens.weight"].astype(jnp.float32)[tokens]
    def layer(lw, h):
        return decoder_layer(cfg, lw, h, linear)

    if remat:
        layer = jax.checkpoint(layer)
    for i in range(cfg["num_hidden_layers"]):
        p = f"layers.{i}."
        lw = {n[len(p):]: a for n, a in w.items() if n.startswith(p)}
        h = layer(lw, h)
    return rms_norm(h, w["norm.weight"], cfg["rms_norm_eps"])


def logits(cfg, w, tokens, linear=f32_linear):
    """[B, S, V] float32 logits of a full causal forward pass."""
    with jax.default_matmul_precision("highest"):
        return linear(hidden_states(cfg, w, tokens, linear), w["lm_head.weight"])


def loss_sum(cfg, w, x, y, linear=f32_linear):
    """Summed next-token cross entropy of rows x against labels y."""
    with jax.default_matmul_precision("highest"):
        hn = hidden_states(cfg, w, x, linear, remat=True)
        lg = linear(hn, w["lm_head.weight"])
        logp = jax.nn.log_softmax(lg, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, y[..., None], axis=-1))


def adamw_step(p, g, m, v, t, lr, beta1=0.9, beta2=0.999, eps=1e-8,
               weight_decay=0.01):
    """Decoupled AdamW (Loshchilov & Hutter), step ``t`` counted from 1."""
    p = p * (1.0 - lr * weight_decay)
    m = beta1 * m + (1.0 - beta1) * g
    v = beta2 * v + (1.0 - beta2) * jnp.square(g)
    mhat = m / (1.0 - beta1 ** t)
    vhat = v / (1.0 - beta2 ** t)
    return p - lr * mhat / (jnp.sqrt(vhat) + eps), m, v
