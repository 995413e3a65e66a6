"""Operations and bytes the algorithm needs, from shapes alone.

Every share of a peak or a roofline in the benchmark divides one of these by a
measured time. They count what the mathematics needs, whatever implements it:
nothing recomputed, nothing padded, no slot that holds no request.

``cfg`` is a configuration file's dict (the published config.json keys).
All counts are multiply-adds times two.
"""
from __future__ import annotations


def head_dim(cfg):
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


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


def attention_flops(cfg, n_query, n_context_sum):
    """QK^T and PV of one layer: 2 matmuls x 2 x head_dim x heads for every
    (query, key) pair that the mask keeps. ``n_context_sum`` is the number of
    such pairs per head, summed over the ``n_query`` queries."""
    del n_query
    return 4 * cfg["num_attention_heads"] * head_dim(cfg) * n_context_sum


def causal_pairs(seq):
    """(query, key) pairs a causal mask keeps in one sequence of ``seq``."""
    return seq * (seq + 1) // 2


def train_flops_per_token(cfg, seq):
    """Forward and backward of one token in a sequence of ``seq``: three
    times the forward's matmuls (layers and head; the embedding is a gather)
    and three times causal attention's two matmuls. Nothing recomputed."""
    layers = cfg["num_hidden_layers"]
    matmul = 2 * (layers * layer_matmul_params(cfg) + head_params(cfg))
    attn = layers * attention_flops(cfg, seq, causal_pairs(seq)) / seq
    return 3 * (matmul + attn)


def prefill_flops(cfg, n_prompt):
    """One prompt of ``n_prompt`` tokens: every layer for every token, causal
    attention, and the head for the last position only (the one token that
    is sampled)."""
    layers = cfg["num_hidden_layers"]
    return (2 * layers * layer_matmul_params(cfg) * n_prompt
            + layers * attention_flops(cfg, n_prompt, causal_pairs(n_prompt))
            + 2 * head_params(cfg))


def decode_flops(cfg, context):
    """One output token whose query sees ``context`` positions (itself
    included): every layer and the head once, attention over the context."""
    layers = cfg["num_hidden_layers"]
    return (2 * (layers * layer_matmul_params(cfg) + head_params(cfg))
            + layers * attention_flops(cfg, 1, context))


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
    return {"flops": layers * attention_flops(cfg, len(contexts), total),
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
    unit = attention_flops(cfg, seq, causal_pairs(seq)) / 2   # one matmul
    flops = batch * layers * 7 * unit
    d = head_dim(cfg)
    q_elems = batch * seq * cfg["num_attention_heads"] * d
    kv_elems = batch * seq * cfg["num_key_value_heads"] * d
    fwd = 2 * q_elems + 2 * kv_elems
    bwd = 4 * q_elems + 4 * kv_elems
    return {"flops": flops, "bytes": layers * (fwd + bwd) * dtype_bytes}


def roofline_seconds(work, peak):
    """Least time for ``work`` on a chip with ``peak``; says which bound."""
    t_flops = work["flops"] / peak["bf16_flops_per_s"]
    t_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
