"""Plain reference of the Falcon-H1 decoder (tiiuae Falcon-H1-34B-Instruct):
the forward pass in straightforward ``jax.numpy`` and float32, under
``jax.default_matmul_precision("highest")``. No kernels, no cache, no
chunks: the recurrence is one ``lax.scan`` over the tokens. Nothing is
imported from the program.

It follows the published ``config.json`` and agrees with the publisher's
code (``transformers`` ``models/falcon_h1/modeling_falcon_h1.py``; held by
``tests/test_falcon_h1.py`` where that can be imported); ``cfg`` is a
configuration file's dict. ``rms(x, w) = x / sqrt(mean(x^2) + rms_norm_eps)
w``; ``silu(a) = a sigmoid(a)``.

- **Model.** ``h = E[ids] embedding_multiplier``; ``num_hidden_layers``
  blocks; ``logits = (rms(h, w_final) W_head) lm_head_multiplier``.
- **Block.** ``u = rms(h, w_in)``; ``h += ssm_out_multiplier Mixer(u) +
  attention_out_multiplier Attn(attention_in_multiplier u)``; ``v = rms(h,
  w_ff)``; ``h += mlp_multipliers[1] ((v W_up) silu(mlp_multipliers[0] (v
  W_gate))) W_down``.
- **Attn.** ``q = a W_q``, ``k = (a W_k) key_multiplier``, ``v = a W_v``,
  no bias; RoPE over all ``head_dim`` dims, halves rotated together,
  ``inv_freq_i = rope_theta^(-2i/head_dim)``; causal softmax attention,
  scale ``head_dim^-1/2``, query head ``j`` reads KV head ``j // (heads /
  kv_heads)``; ``W_o``.
- **Mixer** (``d = mamba_d_ssm``, ``H = mamba_n_heads``, ``P =
  mamba_d_head``, ``G = mamba_n_groups``, ``N = mamba_d_state``, ``K =
  mamba_d_conv``):
  1. ``p = ((ssm_in_multiplier u) W_in) m`` with ``m`` =
     ``ssm_multipliers[0]`` on the first ``d`` columns (z), ``[1]`` on the
     next ``d`` (x), ``[2]`` on the next ``G N`` (B), ``[3]`` on the next
     ``G N`` (C), ``[4]`` on the last ``H`` (dt).
  2. ``c_t = silu(b_conv + sum_k w_conv[:, k] xBC_(t-K+1+k))``, depthwise
     and causal, zeros before the sequence; ``c`` splits into ``x [H, P]``,
     ``B [G, N]``, ``C [G, N]``; head ``i`` uses group ``i // (H / G)``.
  3. ``dt = softplus(dt_raw + dt_bias)``, ``A = -exp(A_log)``;
     ``S_t = exp(dt_t A) S_(t-1) + dt_t x_t (x) B_t`` (``S [H, P, N]``, zero
     before the sequence); ``y_t = S_t C_t + D x_t``.
  4. ``g = y silu(z)``, RMS-normalised within each of the ``G`` groups of
     ``d / G`` channels, times ``w_norm``; then ``W_out``.

Departures from the source: none in the mathematics. The configuration's
``assumed`` lists what the config does not say (the state's precision, how
the weights are drawn). q|k|v and gate|up are stored fused along the
output axis (the benchmark's weight layout, split here).

``forward`` is the mathematics on the leaves it is given. ``logits`` is what
a driver calls with the leaves as ``lib/weights.py`` drew them from the
seed: it first gives four leaves of the mixer the distribution this
architecture states for them (``benchmark/arch/falcon_h1.py::LEAF_DRAW``:
the benchmark's own file, which imports nothing of the program until it
is asked to build the program's model), as the driver's model does for
itself.

To fit beside the bf16 leaves on the chip, attention runs one KV head's
group of query heads at a time (``lax.map``) and the head is applied a
slice of the vocabulary at a time (a column's int8 scale is its own, so the
control is the same slice by slice). ``linear`` is the one place a matrix
is applied; the lower-precision control passes ``int8_linear`` there.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.arch.falcon_h1 import family_leaves

# columns of the head a product: 16 slices of the published vocabulary
_HEAD_COLUMNS = 16320


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


def rope(x, theta):
    """x: [B, S, heads, D] at positions 0..S-1, all D dims rotated."""
    d = x.shape[-1]
    freq = float(theta) ** (-2.0 * jnp.arange(d // 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq[None]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def attention(q, k, v):
    """Causal softmax attention. q: [B, S, n, D]; k, v: [B, S, Hkv, D]."""
    b, s, n, d = q.shape
    hkv = k.shape[2]
    rep = n // hkv
    mask = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]

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


def self_attention(cfg, lw, a, linear):
    n, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    b, s, _ = a.shape
    qkv = linear(a, lw["self_attn.qkv_proj.weight"])
    q, k, v = jnp.split(qkv, [n * d, (n + hkv) * d], axis=-1)
    k = k * cfg["key_multiplier"]
    q = rope(q.reshape(b, s, n, d), cfg["rope_theta"])
    k = rope(k.reshape(b, s, hkv, d), cfg["rope_theta"])
    out = attention(q, k, v.reshape(b, s, hkv, d))
    return linear(out.reshape(b, s, n * d), lw["self_attn.o_proj.weight"])


def causal_conv(xbc, weight, bias):
    """Depthwise causal conv. xbc [B, S, C]; weight [C, K] (tap ``k``
    multiplies the input ``K - 1 - k`` positions back); bias [C]."""
    k = weight.shape[1]
    s = xbc.shape[1]
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    w = weight.astype(jnp.float32)
    out = sum(padded[:, i:i + s] * w[:, i] for i in range(k))
    return out + bias.astype(jnp.float32)


def recurrence(x, dt, a, bmat, cmat, carry=True):
    """The selective scan, one token at a time. x [B, S, H, P]; dt
    [B, S, H]; a [H]; bmat, cmat [B, S, H, N]. Returns y [B, S, H, P]
    (without the skip term). ``carry=False`` forgets the state after every
    token (what the tests measure the carried state's share with)."""
    b, _, h, p = x.shape

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        if not carry:
            state = jnp.zeros_like(state)
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :])
        return state, jnp.sum(state * c_t[..., None, :], axis=-1)

    state0 = jnp.zeros((b, h, p, bmat.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(step, state0, tuple(
        jnp.moveaxis(t, 1, 0) for t in (x, dt, bmat, cmat)))
    return jnp.moveaxis(y, 0, 1)


def mixer(cfg, lw, u, linear, carry=True):
    d, heads, p = cfg["mamba_d_ssm"], cfg["mamba_n_heads"], cfg["mamba_d_head"]
    g, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    b, s, _ = u.shape
    m = cfg["ssm_multipliers"]
    mup = jnp.concatenate([
        jnp.full((d,), m[0]), jnp.full((d,), m[1]), jnp.full((g * n,), m[2]),
        jnp.full((g * n,), m[3]), jnp.full((heads,), m[4])]
    ).astype(jnp.float32)
    proj = linear(u * cfg["ssm_in_multiplier"],
                  lw["mamba.in_proj.weight"]) * mup
    z, xbc, dt_raw = jnp.split(proj, [d, 2 * d + 2 * g * n], axis=-1)
    c = jax.nn.silu(causal_conv(xbc, lw["mamba.conv1d.weight"],
                                lw["mamba.conv1d.bias"]))
    x, bmat, cmat = jnp.split(c, [d, d + g * n], axis=-1)
    x = x.reshape(b, s, heads, p)
    rep = heads // g
    bmat = jnp.repeat(bmat.reshape(b, s, g, n), rep, axis=2)
    cmat = jnp.repeat(cmat.reshape(b, s, g, n), rep, axis=2)
    dt = jax.nn.softplus(dt_raw + lw["mamba.dt_bias"].astype(jnp.float32))
    a = -jnp.exp(lw["mamba.A_log"].astype(jnp.float32))
    y = recurrence(x, dt, a, bmat, cmat, carry)
    y = y + lw["mamba.D"].astype(jnp.float32)[:, None] * x
    gated = y.reshape(b, s, d) * jax.nn.silu(z)
    grouped = gated.reshape(b, s, g, d // g)
    var = jnp.mean(jnp.square(grouped), axis=-1, keepdims=True)
    normed = (grouped * jax.lax.rsqrt(var + cfg["rms_norm_eps"])).reshape(
        b, s, d) * lw["mamba.norm.weight"].astype(jnp.float32)
    return linear(normed, lw["mamba.out_proj.weight"])


def mlp(cfg, lw, v, linear):
    gate, up = jnp.split(linear(v, lw["feed_forward.gate_up_proj.weight"]),
                         2, axis=-1)
    gm, dm = cfg["mlp_multipliers"]
    return linear(up * jax.nn.silu(gate * gm),
                  lw["feed_forward.down_proj.weight"]) * dm


def block(cfg, lw, h, linear):
    u = rms_norm(h, lw["input_layernorm.weight"], cfg["rms_norm_eps"])
    h = (h + cfg["ssm_out_multiplier"] * mixer(cfg, lw, u, linear)
         + cfg["attention_out_multiplier"] * self_attention(
             cfg, lw, u * cfg["attention_in_multiplier"], linear))
    v = rms_norm(h, lw["pre_ff_layernorm.weight"], cfg["rms_norm_eps"])
    return h + mlp(cfg, lw, v, linear)


def layer_weights(w, layer):
    p = f"layers.{layer}."
    return {n[len(p):]: a for n, a in w.items() if n.startswith(p)}


def hidden_states(cfg, w, tokens, linear=f32_linear):
    """Final-norm output [B, S, H] for int tokens [B, S]."""
    h = (w["embed_tokens.weight"][tokens].astype(jnp.float32)
         * cfg["embedding_multiplier"])
    for i in range(cfg["num_hidden_layers"]):
        h = block(cfg, layer_weights(w, i), h, linear)
    return rms_norm(h, w["final_layernorm.weight"], cfg["rms_norm_eps"])


def head(h, weight, linear):
    """``h W_head``, ``_HEAD_COLUMNS`` of the vocabulary at a time where
    they divide it (the float32 copy of the whole published head would be
    5.3 GB)."""
    v = weight.shape[1]
    if v <= _HEAD_COLUMNS or v % _HEAD_COLUMNS:
        return linear(h, weight)

    def put(i, out):
        cols = jax.lax.dynamic_slice_in_dim(
            weight, i * _HEAD_COLUMNS, _HEAD_COLUMNS, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(
            out, linear(h, cols), i * _HEAD_COLUMNS, axis=-1)

    return jax.lax.fori_loop(
        0, v // _HEAD_COLUMNS, put,
        jnp.zeros(h.shape[:-1] + (v,), jnp.float32))


def logits(cfg, w, tokens, linear=f32_linear):
    """``forward`` on the seed's leaves as ``lib/weights.py`` drew them."""
    return forward(cfg, family_leaves(w), tokens, linear)


def forward(cfg, w, tokens, linear=f32_linear):
    """[B, S, V] float32 logits of a full causal forward pass."""
    with jax.default_matmul_precision("highest"):
        return head(hidden_states(cfg, w, tokens, linear),
                    w["lm_head.weight"], linear) * cfg["lm_head_multiplier"]
