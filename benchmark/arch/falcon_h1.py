"""The Falcon-H1 family's decoder (tiiuae Falcon-H1-34B-Instruct): the one
place in the benchmark that knows this architecture's shape. A configuration
names it (``"arch": "falcon_h1"``); the harness, the drivers, the readers
and the tests ask it for the program's model, the weights' leaves, the work
counts, the published values and the CPU rehearsal's widths.

The layer's equations are in ``benchmark/reference/falcon_h1.py``: every
block runs a Mamba-2 mixer and GQA attention side by side on one normed
input and adds them, then a gated MLP; fixed scalar multipliers sit on the
embedding, the head, the keys, both mixers' inputs and outputs, five
sections of the mixer's input projection and two places in the MLP.

Weights' names and layout (``[in, out]`` matrices, q|k|v and gate|up fused
along ``out``): ``embed_tokens.weight [V, H]``; per layer
``input_layernorm.weight [H]``, ``self_attn.qkv_proj.weight [H, (n + 2 Hkv)
D]``, ``self_attn.o_proj.weight [n D, H]``, ``mamba.in_proj.weight [H, 2 d
+ 2 G N + Hm]`` (columns z | x | B | C | dt), ``mamba.conv1d.weight [d + 2
G N, K]``, ``mamba.conv1d.bias [d + 2 G N]``, ``mamba.A_log [Hm]``,
``mamba.D [Hm]``, ``mamba.dt_bias [Hm]``, ``mamba.norm.weight [d]``,
``mamba.out_proj.weight [d, H]``, ``pre_ff_layernorm.weight [H]``,
``feed_forward.gate_up_proj.weight [H, 2 I]``,
``feed_forward.down_proj.weight [I, H]``; ``final_layernorm.weight [H]``,
``lm_head.weight [H, V]``.

How the leaves are drawn: ``lib/weights.py`` draws by rank (one axis: 1 +
0.1 normal; more: normal(0, 0.02)), which would make this family's ``A_log``
and ``dt_bias`` about 1 (a head then forgets by a factor of 0.01-0.06 a
token) and stand a conv bias of 1 beside taps of 0.02 (x, B and C then
nearly constants): the carried state would be a hundredth of the mixer's
output and ``correct`` could see neither its carry nor its precision.
``LEAF_DRAW`` gives four leaves of the mixer the distribution under which
the recurrence does its work, ``family_leaves`` moves the seed's draws
there, and both sides do so for themselves from the same seed:
``build_model``'s model when the driver casts it (``to``), the reference in
``logits``.

The work functions count what the mathematics needs, whatever implements
it: nothing recomputed, nothing padded, no slot that holds no request, the
head for the positions that are sampled. All counts are multiply-adds
times two.
"""
from __future__ import annotations

import jax.numpy as jnp

from benchmark.lib import weights, work as W

# -- what the source publishes, and what may never be cut --------------------
PUBLISHED = {
    "https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/"
    "config.json": {
        "attention_bias": False, "attention_in_multiplier": 1,
        "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
        "embedding_multiplier": 5.656854249492381, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 5120,
        "intermediate_size": 21504, "key_multiplier": 0.011048543456039804,
        "lm_head_multiplier": 0.0078125, "mamba_chunk_size": 128,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 128,
        "mamba_d_ssm": 4096, "mamba_d_state": 256, "mamba_expand": 2,
        "mamba_n_groups": 2, "mamba_n_heads": 32,
        "mamba_norm_before_gate": False, "mamba_proj_bias": False,
        "mamba_rms_norm": True, "mamba_use_mlp": True,
        "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_expansion_factor": 8,
        "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
        "model_type": "falcon_h1", "num_attention_heads": 20,
        "num_hidden_layers": 72, "num_key_value_heads": 4,
        "num_logits_to_keep": 1, "projectors_bias": False,
        "rms_norm_eps": 1e-05, "rope_scaling": None,
        "rope_theta": 100000000000, "ssm_in_multiplier": 0.25,
        "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369,
                            0.5, 0.3535533905932738],
        "ssm_out_multiplier": 0.08838834764831845,
        "tie_word_embeddings": False, "vocab_size": 261120},
}
# every width and every multiplier: only the depth can be cut
WIDTH_KEYS = (
    "hidden_size", "intermediate_size", "head_dim", "num_attention_heads",
    "num_key_value_heads", "vocab_size", "mamba_d_ssm", "mamba_d_state",
    "mamba_d_head", "mamba_n_heads", "mamba_n_groups", "mamba_d_conv",
    "mamba_chunk_size", "mamba_expand", "mlp_expansion_factor",
    "attention_in_multiplier", "attention_out_multiplier",
    "embedding_multiplier", "key_multiplier", "lm_head_multiplier",
    "mlp_multipliers", "ssm_in_multiplier", "ssm_multipliers",
    "ssm_out_multiplier", "rope_theta", "rms_norm_eps")


def tiny(cfg):
    """The keys a CPU rehearsal changes: widths, depth and the engine. Both
    mixers stay in every block, five query heads a KV head, two B/C groups,
    a chunk shorter than the prompts."""
    out = dict(vocab_size=512, hidden_size=128, intermediate_size=256,
               num_hidden_layers=2, num_attention_heads=10,
               num_key_value_heads=2, head_dim=16, mamba_d_ssm=64,
               mamba_n_heads=4, mamba_d_head=16, mamba_d_state=16,
               mamba_n_groups=2, mamba_chunk_size=8,
               max_position_embeddings=256)
    if "engine" in cfg:
        # float32 here, so that the program sits far inside the limit that
        # the int8 control has to break
        out.update(engine={"block_size": 16, "max_slots": 4,
                           "max_model_len": 128}, dtype="float32")
    return out


# -- how four leaves of the mixer are drawn --------------------------------------
# (mean, std) of a normal draw, by the leaf's name inside its block. Steps
# ``dt = softplus(dt_bias)`` of 0.0037-0.027 and decays ``A = exp(A_log)``
# of 0.11-0.82 (at two sigma): a head forgets by 0.98-0.9996 a token and
# remembers some 50 to 2,500 tokens, the upper half of what a trained
# model's heads span (0.5-0.999), so that a state carried over a context of
# 200-1,536 tokens is most of the mixer's output and a step's increment is
# a few thousandths of the state (what a bf16 state cannot add). Conv taps
# and bias with the spread of the publisher's own ``nn.Conv1d`` (uniform
# +-0.5: std 0.29). ``D`` stays as the rank rule draws it (1 + 0.1 normal:
# the family's 1). One mixer at the published widths in float32, 512 tokens
# (PERF.md section 4): without the carried state its output changes by 76%,
# from a zero state at decode by 85%, with a state that 170 pad tokens
# updated by 24%, with a bf16 state by 9.9%; the program's own bf16 rounding
# is 0.46%.
LEAF_DRAW = {
    "mamba.A_log": (-1.2, 0.5),
    "mamba.dt_bias": (-4.6, 0.5),
    "mamba.conv1d.weight": (0.0, 0.29),
    "mamba.conv1d.bias": (0.0, 0.29),
}


def family_leaves(w):
    """``w`` (name -> leaf, as ``lib/weights.py`` drew them: all, or some)
    with the leaves ``LEAF_DRAW`` names moved to their distribution: the
    same normal deviate, another mean and spread, in the leaf's dtype.
    Every other leaf is the array it was."""
    out = dict(w)
    for name, leaf in w.items():
        draw = LEAF_DRAW.get(name.split(".", 2)[-1])
        if draw is None:
            continue
        mean, std = draw
        base, spread = ((1.0, weights.NORM_JITTER) if leaf.ndim == 1
                        else (0.0, weights.INIT_STD))
        deviate = (leaf.astype(jnp.float32) - base) / spread
        out[name] = (mean + std * deviate).astype(leaf.dtype)
    return out


# -- the program's model -------------------------------------------------------
def build_model(cfg, max_positions):
    """The program's serving model; its parameters' names are ``shapes``'s.
    A driver puts the seed's leaves into it as ``lib/weights.py`` draws
    them and then casts it (``to``, its last call before the engine reads
    the parameters): there ``LEAF_DRAW``'s leaves get their distribution,
    once. The model is the program's in everything else. (When
    ``lib/weights.py`` asks an architecture how a leaf is drawn, PERF.md
    section 7 q, ``LEAF_DRAW`` is what it reads, and this override and the
    reference's call of ``family_leaves`` go.)"""
    from paddle_tpu.models import FalconH1Config, FalconH1ForCausalLM

    class SeededFalconH1(FalconH1ForCausalLM):
        _family_leaves_in_place = False

        def to(self, *args, **kwargs):
            if not self._family_leaves_in_place:
                named = dict(self.named_parameters())
                moved = family_leaves({n: p._value for n, p in named.items()})
                for name, p in named.items():
                    p._value = moved[name]
                self._family_leaves_in_place = True
            return super().to(*args, **kwargs)

    return SeededFalconH1(FalconH1Config.from_dict(
        dict(cfg, max_position_embeddings=max_positions)))


# -- the weights' leaves, in a fixed order -------------------------------------
def conv_dim(cfg):
    return cfg["mamba_d_ssm"] + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]


def shapes(cfg):
    h, d = cfg["hidden_size"], cfg["head_dim"]
    n, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    ssm, hm = cfg["mamba_d_ssm"], cfg["mamba_n_heads"]
    inter = cfg["intermediate_size"]
    out = {"embed_tokens.weight": (cfg["vocab_size"], h)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"layers.{i}."
        out[p + "input_layernorm.weight"] = (h,)
        out[p + "self_attn.qkv_proj.weight"] = (h, (n + 2 * hkv) * d)
        out[p + "self_attn.o_proj.weight"] = (n * d, h)
        out[p + "mamba.in_proj.weight"] = (h, ssm + conv_dim(cfg) + hm)
        out[p + "mamba.conv1d.weight"] = (conv_dim(cfg), cfg["mamba_d_conv"])
        out[p + "mamba.conv1d.bias"] = (conv_dim(cfg),)
        out[p + "mamba.A_log"] = (hm,)
        out[p + "mamba.D"] = (hm,)
        out[p + "mamba.dt_bias"] = (hm,)
        out[p + "mamba.norm.weight"] = (ssm,)
        out[p + "mamba.out_proj.weight"] = (ssm, h)
        out[p + "pre_ff_layernorm.weight"] = (h,)
        out[p + "feed_forward.gate_up_proj.weight"] = (h, 2 * inter)
        out[p + "feed_forward.down_proj.weight"] = (inter, h)
    out["final_layernorm.weight"] = (h,)
    out["lm_head.weight"] = (h, cfg["vocab_size"])
    return out


# -- work counts ---------------------------------------------------------------
def layer_matmul_params(cfg):
    """Weights of one block that a token is multiplied by: q, k, v, o; the
    mixer's two projections; the gated MLP."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    n, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    ssm = cfg["mamba_d_ssm"]
    return (h * (n + 2 * hkv) * d + n * d * h
            + h * (ssm + conv_dim(cfg) + cfg["mamba_n_heads"]) + ssm * h
            + 3 * h * cfg["intermediate_size"])


def head_params(cfg):
    return cfg["hidden_size"] * cfg["vocab_size"]


def state_elements(cfg):
    """Elements of one sequence's recurrent state in one layer."""
    return cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"]


def attention_flops(cfg, pairs):
    """QK^T and PV of one layer for ``pairs`` (query, key) pairs a head."""
    return 4 * cfg["num_attention_heads"] * cfg["head_dim"] * pairs


def recurrence_flops(cfg, tokens):
    """The recurrence of one layer, token by token: the decay, the outer
    product added, the read-out (three multiply-adds a state element)."""
    return 6 * state_elements(cfg) * tokens


def prefill_flops(cfg, n_prompt):
    """One prompt of ``n_prompt`` tokens: every block for every token,
    causal attention and the recurrence, the head for the last position."""
    layers = cfg["num_hidden_layers"]
    return (layers * (2 * layer_matmul_params(cfg) * n_prompt
                      + attention_flops(cfg, W.causal_pairs(n_prompt))
                      + recurrence_flops(cfg, n_prompt))
            + 2 * head_params(cfg))


def decode_flops(cfg, context):
    """One output token whose query sees ``context`` positions (itself
    included)."""
    layers = cfg["num_hidden_layers"]
    return (layers * (2 * layer_matmul_params(cfg)
                      + attention_flops(cfg, context)
                      + recurrence_flops(cfg, 1))
            + 2 * head_params(cfg))


def paged_attention_decode(cfg, contexts, dtype_bytes=2):
    """Decode attention over a paged cache, for output tokens whose queries
    see ``contexts`` positions each: the live K and V are read once, queries
    and outputs are small beside them. Returns {"flops", "bytes"} over all
    layers."""
    total = sum(contexts)
    layers = cfg["num_hidden_layers"]
    kv_token = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * dtype_bytes
    qo = (2 * len(contexts) * cfg["num_attention_heads"] * cfg["head_dim"]
          * dtype_bytes)
    return {"flops": layers * attention_flops(cfg, total),
            "bytes": layers * (total * kv_token + qo)}


def ssm_state_update_decode(cfg, tokens, state_bytes=4):
    """The recurrence's step for ``tokens`` output tokens in every layer: a
    sequence's state is read once and written once a token and layer (in
    the precision the configuration keeps it in); the token's x, B, C, dt
    and y are small beside it. Returns {"flops", "bytes"}."""
    layers = cfg["num_hidden_layers"]
    return {"flops": layers * recurrence_flops(cfg, tokens),
            "bytes": layers * tokens * 2 * state_elements(cfg) * state_bytes}


def ssd_prefill(cfg, prompt_lens, dtype_bytes=2):
    """The recurrence over whole prompts from a zero state in its chunked
    form (chunks of ``mamba_chunk_size``), in every layer. FLOPs of a
    chunk's four products: ``C B^T`` over the causal pairs (once a group),
    that times the decays applied to ``dt x`` (a head), the entering state
    read out (a head; not in a prompt's first chunk, where it is zero) and
    the state brought forward (a head). Bytes: x, B, C, dt read and y
    written once. Returns {"flops", "bytes"}."""
    heads, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    g, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    chunk = cfg["mamba_chunk_size"]
    flops = 0
    for length in prompt_lens:
        for at in range(0, length, chunk):
            q = min(chunk, length - at)
            pairs = W.causal_pairs(q)
            flops += 2 * pairs * (g * n + heads * p)
            flops += 2 * q * n * p * heads * (2 if at else 1)
    per_token = (2 * heads * p + 2 * g * n) * dtype_bytes + 4 * heads
    layers = cfg["num_hidden_layers"]
    return {"flops": layers * flops,
            "bytes": layers * sum(prompt_lens) * per_token}
