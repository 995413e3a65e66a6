"""The Llama family's decoder (Mistral-7B-v0.3 is one): the one place in the
benchmark that knows this architecture's shape. A configuration names it
(``"arch": "llama"``); the harness, the drivers, the readers and the tests
ask it for the program's model and trainer, the weights' leaves, the work
counts, the published values and the CPU rehearsal's widths.

Weights' names and layout (the benchmark's own; ``[in, out]`` matrices, q|k|v
and gate|up fused along ``out``): ``embed_tokens.weight [V, H]``,
``layers.<i>.input_layernorm.weight [H]``,
``layers.<i>.self_attn.qkv_proj.weight [H, (Hq + 2 Hkv) D]``,
``layers.<i>.self_attn.o_proj.weight [Hq D, H]``,
``layers.<i>.post_attention_layernorm.weight [H]``,
``layers.<i>.mlp.gate_up_proj.weight [H, 2 I]``,
``layers.<i>.mlp.down_proj.weight [I, H]``, ``norm.weight [H]``,
``lm_head.weight [H, V]``.

The work functions count what the mathematics needs, whatever implements it:
nothing recomputed, nothing padded, no slot that holds no request. ``cfg`` is
a configuration file's dict (the published config.json keys). All counts are
multiply-adds times two.
"""
from __future__ import annotations

from benchmark.lib.work import causal_pairs

# -- what the source publishes, and what may never be cut --------------------
PUBLISHED = {
    "https://huggingface.co/mistralai/Mistral-7B-v0.3/blob/main/config.json": {
        "vocab_size": 32768, "hidden_size": 4096, "intermediate_size": 14336,
        "num_hidden_layers": 32, "num_attention_heads": 32,
        "num_key_value_heads": 8, "rope_theta": 1e6, "rms_norm_eps": 1e-5,
        "max_position_embeddings": 32768, "tie_word_embeddings": False,
        "sliding_window": None},
}
WIDTH_KEYS = ("hidden_size", "intermediate_size", "head_dim")


def tiny(cfg):
    """The keys a CPU rehearsal changes: widths, depth and the engine."""
    out = dict(vocab_size=512, hidden_size=128, intermediate_size=256,
               num_attention_heads=8, num_key_value_heads=2, head_dim=16,
               max_position_embeddings=256, num_hidden_layers=2)
    if "engine" in cfg:
        # float32 here, so that the program sits far inside the limit that
        # the int8 control has to break
        out.update(engine={"block_size": 16, "max_slots": 4,
                           "max_model_len": 128}, dtype="float32")
    return out


# -- the program's model and trainer -----------------------------------------
def llama_config(cfg, max_positions):
    from paddle_tpu.models import LlamaConfig

    return LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        max_position_embeddings=max_positions,
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        tie_word_embeddings=cfg.get("tie_word_embeddings", False))


def build_model(cfg, max_positions):
    """The program's serving model; its parameters' names are ``shapes``'s."""
    from paddle_tpu.models import LlamaForCausalLM

    return LlamaForCausalLM(llama_config(cfg, max_positions))


def build_trainer(cfg, mesh, optimizer):
    from paddle_tpu.models.llama_pipeline import LlamaPipelineTrainer

    tr = cfg["trainer"]
    return LlamaPipelineTrainer(
        llama_config(cfg, cfg["max_position_embeddings"]), mesh, optimizer,
        n_micro=tr["n_micro"], zero_stage=tr["zero_stage"], seed=0)


def trainer_layout(flat, cfg):
    """The flat leaves in the trainer's layout: decoder blocks stacked
    [stages=1, layers, ...] under ``blocks.``, and embed/norm/head."""
    import jax.numpy as jnp

    out = {"embed.weight": flat["embed_tokens.weight"],
           "norm.weight": flat["norm.weight"],
           "head.weight": flat["lm_head.weight"]}
    keys = [n[len("layers.0."):] for n in flat if n.startswith("layers.0.")]
    for k in keys:
        out["blocks." + k] = jnp.stack(
            [flat[f"layers.{i}.{k}"]
             for i in range(cfg["num_hidden_layers"])])[None]
    return out


def trainer_leaf_norms(tree):
    """Per-leaf L2 norms of a tree in the trainer's layout, under the flat
    names; a stacked block leaf gives one norm per layer."""
    import jax.numpy as jnp

    out = {}
    for n, a in tree.items():
        a = a.astype(jnp.float32)
        if n.startswith("blocks."):
            per = jnp.sqrt(jnp.sum(jnp.square(a), axis=tuple(range(2, a.ndim))))
            for i in range(a.shape[1]):
                out[f"layers.{i}.{n[len('blocks.'):]}"] = per[0, i]
        else:
            flat = {"embed.weight": "embed_tokens.weight",
                    "head.weight": "lm_head.weight"}.get(n, n)
            out[flat] = jnp.sqrt(jnp.sum(jnp.square(a)))
    return out


# -- the weights' leaves, in a fixed order -------------------------------------
def head_dim(cfg):
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def shapes(cfg):
    h, d = cfg["hidden_size"], head_dim(cfg)
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


# -- work counts ---------------------------------------------------------------
def layer_matmul_params(cfg):
    """Weights of one decoder layer that a token is multiplied by: q, k, v, o
    and the gated MLP's three matrices (norm weights are elementwise)."""
    h, d = cfg["hidden_size"], head_dim(cfg)
    q = h * cfg["num_attention_heads"] * d
    kv = 2 * h * cfg["num_key_value_heads"] * d
    o = cfg["num_attention_heads"] * d * h
    mlp = 3 * h * cfg["intermediate_size"]
    return q + kv + o + mlp


def head_params(cfg):
    return cfg["hidden_size"] * cfg["vocab_size"]


def num_params(cfg):
    """All parameters: layers (with two norms each), embedding, final norm,
    head (untied)."""
    layer = layer_matmul_params(cfg) + 2 * cfg["hidden_size"]
    emb = cfg["vocab_size"] * cfg["hidden_size"]
    head = 0 if cfg.get("tie_word_embeddings") else head_params(cfg)
    return cfg["num_hidden_layers"] * layer + emb + cfg["hidden_size"] + head


def attention_flops(cfg, n_context_sum):
    """QK^T and PV of one layer: 2 matmuls x 2 x head_dim x heads for every
    (query, key) pair that the mask keeps. ``n_context_sum`` is the number of
    such pairs per head, summed over the queries."""
    return 4 * cfg["num_attention_heads"] * head_dim(cfg) * n_context_sum


def train_flops_per_token(cfg, seq):
    """Forward and backward of one token in a sequence of ``seq``: three
    times the forward's matmuls (layers and head; the embedding is a gather)
    and three times causal attention's two matmuls. Nothing recomputed."""
    layers = cfg["num_hidden_layers"]
    matmul = 2 * (layers * layer_matmul_params(cfg) + head_params(cfg))
    attn = layers * attention_flops(cfg, causal_pairs(seq)) / seq
    return 3 * (matmul + attn)


def prefill_flops(cfg, n_prompt):
    """One prompt of ``n_prompt`` tokens: every layer for every token, causal
    attention, and the head for the last position only (the one token that
    is sampled)."""
    layers = cfg["num_hidden_layers"]
    return (2 * layers * layer_matmul_params(cfg) * n_prompt
            + layers * attention_flops(cfg, causal_pairs(n_prompt))
            + 2 * head_params(cfg))


def decode_flops(cfg, context):
    """One output token whose query sees ``context`` positions (itself
    included): every layer and the head once, attention over the context."""
    layers = cfg["num_hidden_layers"]
    return (2 * (layers * layer_matmul_params(cfg) + head_params(cfg))
            + layers * attention_flops(cfg, context))


def kv_bytes_per_token(cfg, dtype_bytes=2):
    """K and V of one position over all layers."""
    return (2 * cfg["num_key_value_heads"] * head_dim(cfg) * dtype_bytes
            * cfg["num_hidden_layers"])


def paged_attention_decode(cfg, contexts, dtype_bytes=2):
    """Decode attention over a paged cache, for output tokens whose queries
    see ``contexts`` positions each: the live K and V are read once, queries
    and outputs are small beside them. Bound by bytes on any chip whose
    FLOP:byte ratio is above 2 x rep (4 query heads share a KV head here).
    Returns {"flops", "bytes"} over all layers."""
    total = sum(contexts)
    layers = cfg["num_hidden_layers"]
    qo = (2 * len(contexts) * cfg["num_attention_heads"] * head_dim(cfg)
          * dtype_bytes * layers)
    return {"flops": layers * attention_flops(cfg, total),
            "bytes": total * kv_bytes_per_token(cfg, dtype_bytes) + qo}


def flash_attention_train(cfg, batch, seq, dtype_bytes=2):
    """Causal flash attention, forward and backward, of ``batch`` sequences
    of ``seq`` in every layer. Matmuls over the kept half of the S x S
    square: forward QK^T and PV; backward dV, dP, dQ, dK and QK^T once more,
    which any backward pass that does not keep the S x S probabilities has to
    redo (the usual 2.5 x forward convention). Bytes: q, k, v, o read or
    written by the forward; q, k, v, o, do read and dq, dk, dv written by the
    backward; K and V have the KV heads' width."""
    layers = cfg["num_hidden_layers"]
    unit = attention_flops(cfg, causal_pairs(seq)) / 2   # one matmul
    flops = batch * layers * 7 * unit
    d = head_dim(cfg)
    q_elems = batch * seq * cfg["num_attention_heads"] * d
    kv_elems = batch * seq * cfg["num_key_value_heads"] * d
    fwd = 2 * q_elems + 2 * kv_elems
    bwd = 4 * q_elems + 4 * kv_elems
    return {"flops": flops, "bytes": layers * (fwd + bwd) * dtype_bytes}
