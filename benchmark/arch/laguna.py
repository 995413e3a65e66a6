"""The Laguna family's decoder (poolside Laguna-XS.2): the one place in the
benchmark that knows this architecture's shape. A configuration names it
(``"arch": "laguna"``); the harness, the drivers, the readers and the tests
ask it for the program's model, the weights' leaves, the work counts, the
published values and the CPU rehearsal's widths.

The layer's equations are in ``benchmark/reference/laguna.py``. Per-layer
lists (``layer_types``, ``mlp_layer_types``,
``num_attention_heads_per_layer``) say each layer's attention (full, or a
window of ``sliding_window``), MLP (dense, or sparse experts) and query
heads; ``num_experts`` is both the router's width and the experts held here
(the serving cut holds them all).

Weights' names and layout (``[in, out]`` matrices, q|k|v and gate|up fused
along ``out``, experts stacked in front): ``embed_tokens.weight [V, H]``;
per layer ``input_layernorm.weight [H]``, ``self_attn.qkv_proj.weight
[H, (n + 2 Hkv) D]``, ``self_attn.gate_proj.weight [H, n]``,
``self_attn.o_proj.weight [n D, H]``, ``post_attention_layernorm.weight
[H]``; a dense layer's ``mlp.gate_up_proj.weight [H, 2 I]``,
``mlp.down_proj.weight [I, H]``; a sparse layer's ``mlp.router.weight
[H, E]``, ``mlp.gate_up_proj [E, H, 2 Ie]``, ``mlp.down_proj [E, Ie, H]``,
``mlp.shared_expert.gate_up_proj.weight [H, 2 Is]``,
``mlp.shared_expert.down_proj.weight [Is, H]``; ``norm.weight [H]``,
``lm_head.weight [H, V]``.

The work functions count what the mathematics needs, whatever implements
it: nothing recomputed, nothing padded, no slot that holds no request, a
token's own ``num_experts_per_tok`` experts and the shared one, no key
outside a window. All counts are multiply-adds times two.
"""
from __future__ import annotations

_PERIOD = ["full_attention", "sliding_attention", "sliding_attention",
           "sliding_attention"]

# -- what the source publishes, and what may never be cut --------------------
PUBLISHED = {
    "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json": {
        "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
        "intermediate_size": 8192, "num_hidden_layers": 40,
        "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
        "max_position_embeddings": 262144, "attention_bias": False,
        "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 8,
        "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
                "original_max_position_embeddings": 4096, "beta_slow": 1,
                "beta_fast": 64, "attention_factor": 1.4158883083359672,
                "partial_rotary_factor": 0.5},
            "sliding_attention": {
                "rope_type": "default", "rope_theta": 10000,
                "partial_rotary_factor": 1},
            "original_max_position_embeddings": 4096},
        "layer_types": _PERIOD * 10,
        "moe_apply_router_weight_on_input": False,
        "partial_rotary_factor": 0.5,
        "mlp_layer_types": ["dense"] + ["sparse"] * 39,
        "moe_routed_scaling_factor": 2.5,
        "num_attention_heads_per_layer": [48, 64, 64, 64] * 10},
}
WIDTH_KEYS = ("hidden_size", "head_dim", "intermediate_size",
              "moe_intermediate_size", "shared_expert_intermediate_size",
              "num_experts_per_tok", "sliding_window")


def tiny(cfg):
    """The keys a CPU rehearsal changes: widths, depth, a few experts, a
    window shorter than the contexts, and the engine. The layer pattern (a
    leading dense layer, one whole period, 6 and 8 query heads a KV head)
    stays."""
    out = dict(vocab_size=512, hidden_size=128, intermediate_size=256,
               num_attention_heads=12, num_key_value_heads=2, head_dim=16,
               num_attention_heads_per_layer=[12, 16, 16, 16, 12],
               max_position_embeddings=256, num_experts=8,
               num_experts_per_tok=2, moe_intermediate_size=128,
               shared_expert_intermediate_size=128, sliding_window=16)
    if "engine" in cfg:
        # float32 here, so that the program sits far inside the limit that
        # the int8 control has to break
        out.update(engine={"block_size": 16, "max_slots": 4,
                           "max_model_len": 128}, dtype="float32")
    return out


# -- the program's model -------------------------------------------------------
def build_model(cfg, max_positions):
    """The program's serving model; its parameters' names are ``shapes``'s."""
    from paddle_tpu.models import LagunaConfig, LagunaForCausalLM

    return LagunaForCausalLM(LagunaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], max_position_embeddings=max_positions,
        rms_norm_eps=cfg["rms_norm_eps"],
        sliding_window=cfg["sliding_window"], num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        shared_expert_intermediate_size=cfg[
            "shared_expert_intermediate_size"],
        moe_routed_scaling_factor=cfg["moe_routed_scaling_factor"],
        layer_types=list(cfg["layer_types"]),
        mlp_layer_types=list(cfg["mlp_layer_types"]),
        num_attention_heads_per_layer=list(
            cfg["num_attention_heads_per_layer"]),
        rope_parameters=cfg["rope_parameters"]))


# -- the weights' leaves, in a fixed order -------------------------------------
def shapes(cfg):
    h, d, hkv = cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"]
    e, ie = cfg["num_experts"], cfg["moe_intermediate_size"]
    out = {"embed_tokens.weight": (cfg["vocab_size"], h)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"layers.{i}."
        n = cfg["num_attention_heads_per_layer"][i]
        out[p + "input_layernorm.weight"] = (h,)
        out[p + "self_attn.qkv_proj.weight"] = (h, (n + 2 * hkv) * d)
        out[p + "self_attn.gate_proj.weight"] = (h, n)
        out[p + "self_attn.o_proj.weight"] = (n * d, h)
        out[p + "post_attention_layernorm.weight"] = (h,)
        if cfg["mlp_layer_types"][i] == "dense":
            out[p + "mlp.gate_up_proj.weight"] = (
                h, 2 * cfg["intermediate_size"])
            out[p + "mlp.down_proj.weight"] = (cfg["intermediate_size"], h)
        else:
            shared = cfg["shared_expert_intermediate_size"]
            out[p + "mlp.router.weight"] = (h, e)
            out[p + "mlp.gate_up_proj"] = (e, h, 2 * ie)
            out[p + "mlp.down_proj"] = (e, ie, h)
            out[p + "mlp.shared_expert.gate_up_proj.weight"] = (h, 2 * shared)
            out[p + "mlp.shared_expert.down_proj.weight"] = (shared, h)
    out["norm.weight"] = (h,)
    out["lm_head.weight"] = (h, cfg["vocab_size"])
    return out


# -- work counts ---------------------------------------------------------------
def _window(cfg, layer):
    return (cfg["sliding_window"]
            if cfg["layer_types"][layer] == "sliding_attention" else None)


def expert_params(cfg):
    """One routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_matmul_params(cfg, layer):
    """Weights of decoder layer ``layer`` that one token is multiplied by:
    q, k, v, the head gate, o, and its MLP: the dense one, or the router,
    the token's own experts and the shared expert."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    n = cfg["num_attention_heads_per_layer"][layer]
    attn = h * n * d + 2 * h * cfg["num_key_value_heads"] * d + h * n + n * d * h
    if cfg["mlp_layer_types"][layer] == "dense":
        return attn + 3 * h * cfg["intermediate_size"]
    return (attn + h * cfg["num_experts"]
            + cfg["num_experts_per_tok"] * expert_params(cfg)
            + 3 * h * cfg["shared_expert_intermediate_size"])


def head_params(cfg):
    return cfg["hidden_size"] * cfg["vocab_size"]


def seen_pairs(seq, window):
    """(query, key) pairs of one sequence of ``seq`` that a causal mask, and
    a window of the latest ``window`` positions if given, keep."""
    if window is None or seq <= window:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def attention_flops(cfg, layer, pairs):
    """QK^T and PV of layer ``layer`` for ``pairs`` kept (query, key) pairs
    a head."""
    return (4 * cfg["num_attention_heads_per_layer"][layer]
            * cfg["head_dim"] * pairs)


def prefill_flops(cfg, n_prompt):
    """One prompt of ``n_prompt`` tokens: every layer for every token, causal
    attention within each layer's window, the head for the last position
    only."""
    layers = range(cfg["num_hidden_layers"])
    return (sum(2 * layer_matmul_params(cfg, l) * n_prompt
                + attention_flops(cfg, l,
                                  seen_pairs(n_prompt, _window(cfg, l)))
                for l in layers)
            + 2 * head_params(cfg))


def decode_flops(cfg, context):
    """One output token whose query sees ``context`` positions (itself
    included), ``min(context, window)`` of them in a window layer."""
    layers = range(cfg["num_hidden_layers"])
    return (sum(2 * layer_matmul_params(cfg, l)
                + attention_flops(cfg, l, min(context,
                                              _window(cfg, l) or context))
                for l in layers)
            + 2 * head_params(cfg))


def paged_attention_decode(cfg, contexts, dtype_bytes=2):
    """Decode attention over a paged cache, for output tokens whose queries
    see ``contexts`` positions each: the K and V a layer's mask keeps are
    read once (a window layer's at most ``sliding_window`` positions),
    queries and outputs are small beside them.
    Returns {"flops", "bytes"} over all layers."""
    kv_token = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * dtype_bytes
    flops = nbytes = 0
    for l in range(cfg["num_hidden_layers"]):
        w = _window(cfg, l)
        seen = sum(min(c, w or c) for c in contexts)
        flops += attention_flops(cfg, l, seen)
        nbytes += seen * kv_token + (
            2 * len(contexts) * cfg["num_attention_heads_per_layer"][l]
            * cfg["head_dim"] * dtype_bytes)
    return {"flops": flops, "bytes": nbytes}


def sparse_layers(cfg):
    return sum(t == "sparse" for t in cfg["mlp_layer_types"])


def moe_experts(cfg, pairs, experts_read, dtype_bytes=2):
    """The routed experts' two grouped products, for ``pairs`` (token,
    expert) rows in all and ``experts_read`` expert weight sets read in all
    (an expert that several rows of one step and layer share is read once:
    the distinct experts, summed over steps and sparse layers). FLOPs of the
    rows; bytes of those weights and of the rows in and out of both
    products. The router and the shared expert are other matmuls, not this
    kernel's. Returns {"flops", "bytes"}."""
    h, ie = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = pairs * (h + 2 * ie + ie + h)
    return {"flops": 2 * pairs * expert_params(cfg),
            "bytes": (experts_read * expert_params(cfg) + rows) * dtype_bytes}
