"""``models/laguna.py`` through the serving engine on the CPU at a tiny
size: the engine against an uncached forward pass with contexts past the
window (prefill, decode, a prefix-cache tail), the per-layer cache
description the engine sizes its pool from, the model's own counters in
``stats()``, and YaRN's table against numbers written out here."""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu
from paddle_tpu.models import (LagunaConfig, LagunaForCausalLM,
                               LlamaForCausalLM, laguna_tiny, llama_tiny)
from paddle_tpu.models.laguna import laguna_rope_tables
from paddle_tpu.serving import (CacheLayer, DenseKVCache, LLMEngine,
                                SamplingParams)

WINDOW = 8


@pytest.fixture(scope="module")
def model():
    paddle_tpu.seed(11)
    return LagunaForCausalLM(laguna_tiny(seq=96, window=WINDOW))


@pytest.fixture
def fresh_timeline():
    """The decode StepTimeline is the process's, not an engine's: what an
    earlier test's engine booked would be in this one's window."""
    from paddle_tpu import telemetry

    telemetry.step_timeline("decode").clear()


def _prompts(*lens):
    return [np.random.RandomState(n).randint(1, 256, n).tolist()
            for n in lens]


def _assert_greedy(model, prompt, out):
    """``out`` is what greedy decoding of ``prompt`` gives, by one uncached
    forward pass over both: each token is the argmax at the one before."""
    seq = prompt + out
    logits = np.asarray(model(paddle_tpu.to_tensor(
        jnp.asarray([seq[:-1]], jnp.int32)))._value)[0]
    want = logits[len(prompt) - 1:].argmax(-1).tolist()
    assert out == want


def test_published_defaults_and_per_layer_lists():
    c = LagunaConfig()
    assert (c.num_hidden_layers, c.num_experts, c.num_experts_per_tok) == (
        40, 256, 8)
    assert c.layer_types[:5] == ["full_attention"] + ["sliding_attention"] * 3 \
        + ["full_attention"]
    assert c.mlp_layer_types[:2] == ["dense", "sparse"]
    assert c.num_attention_heads_per_layer[:5] == [48, 64, 64, 64, 48]
    with pytest.raises(ValueError, match="layer_types"):
        LagunaConfig(num_hidden_layers=5, layer_types=["full_attention"])


def test_cache_layers_describe_each_layer(model):
    layers = model.cache_layers()
    assert layers == [CacheLayer(2, 16, None), CacheLayer(2, 16, WINDOW),
                      CacheLayer(2, 16, WINDOW), CacheLayer(2, 16, WINDOW),
                      CacheLayer(2, 16, None)]
    llama = LlamaForCausalLM(llama_tiny(layers=2))
    assert llama.cache_layers() == [CacheLayer(2, 16, None)] * 2


def test_engine_equals_uncached_forward_past_the_window(model):
    """Greedy tokens of prompts of 5, 20 and 33 (the last two past the
    window of 8 at prefill, all of them during decode), three to a batch,
    against a full forward pass a token."""
    eng = LLMEngine(model, block_size=8, max_slots=3, max_model_len=96)
    sp = SamplingParams(max_new_tokens=24, temperature=0.0)
    prompts = _prompts(5, 20, 33)
    outs = eng.generate(prompts, sp)
    for p, o in zip(prompts, outs):
        assert len(o) == 24
        _assert_greedy(model, p, o)
    assert eng.decode_traces == 1
    assert eng.stats()["num_failed"] == 0


def test_a_prefix_cache_tail_keeps_the_window(model):
    """The second prompt shares 24 tokens (three blocks) with the first:
    its tail is prefilled against cached blocks, and a window layer's tail
    queries see the prefix's last positions only."""
    eng = LLMEngine(model, block_size=8, max_slots=2, max_model_len=96)
    sp = SamplingParams(max_new_tokens=10, temperature=0.0)
    first = _prompts(30)[0]
    second = first[:24] + _prompts(13)[0]
    a = eng.generate([first], sp)[0]
    b = eng.generate([second], sp)[0]
    assert eng.stats()["prefix_cache"]["hits"] >= 1
    _assert_greedy(model, first, a)
    _assert_greedy(model, second, b)


def test_prefill_attention_through_the_kernel_serves_the_same_tokens(
        model, monkeypatch):
    """Prompts of 20 and 33 (buckets 32 and 64, past the window of 8),
    greedy, through the flash kernel's forward (interpret mode, blocks of
    16: two and four a prompt, each wider than the window) and through the
    einsum composition: the same tokens. Each prefill trace's five
    attention layers are counted on the path they took."""
    from paddle_tpu import kernels
    from paddle_tpu.kernels import flash_attention as fa

    sp = SamplingParams(max_new_tokens=8, temperature=0.0)
    prompts = _prompts(20, 33)
    runs = {}
    for path in ("materialised", "kernel"):
        if path == "kernel":
            monkeypatch.setattr(fa, "PREFILL_BLOCK", 16)
            monkeypatch.setattr(kernels, "prefill_attention_impl",
                                lambda length: fa.flash_attention_prefill)
        eng = LLMEngine(model, block_size=8, max_slots=2, max_model_len=96)
        runs[path] = eng.generate(prompts, sp)
        assert sorted(eng.prefill_traces) == [32, 64]
        assert eng.stats()["perf"]["prefill"]["attention"] == {
            "kernel": 0, "materialised": 0, path: 5 * 2}
    assert runs["kernel"] == runs["materialised"]
    for p, o in zip(prompts, runs["kernel"]):
        _assert_greedy(model, p, o)


def test_dense_cache_decode_agrees_with_the_full_forward(model):
    toks = jnp.asarray([_prompts(21)[0]], jnp.int32)
    full = np.asarray(model(paddle_tpu.to_tensor(toks))._value)
    cache = DenseKVCache(model.config.num_hidden_layers,
                         windows=[l.window for l in model.cache_layers()])
    got = np.asarray(model(paddle_tpu.to_tensor(toks[:, :15]),
                           cache=cache)._value)
    np.testing.assert_allclose(got, full[:, :15], atol=1e-5)
    got = np.asarray(model(paddle_tpu.to_tensor(toks[:, 15:]), cache=cache,
                           positions=paddle_tpu.to_tensor(
                               jnp.arange(15, 21, dtype=jnp.int32)[None])
                           )._value)
    np.testing.assert_allclose(got, full[:, 15:], atol=1e-5)


def test_one_pool_needs_one_kv_width(model, monkeypatch):
    mixed = model.cache_layers()
    mixed[1] = CacheLayer(4, 16, WINDOW)
    monkeypatch.setattr(model, "cache_layers", lambda: mixed)
    with pytest.raises(ValueError, match="one pool"):
        LLMEngine(model, block_size=8, max_slots=2, max_model_len=64)


def test_the_models_counters_reach_stats(model, fresh_timeline):
    eng = LLMEngine(model, block_size=8, max_slots=4, max_model_len=96)
    sp = SamplingParams(max_new_tokens=12, temperature=0.0)
    eng.generate(_prompts(9, 30), sp)
    step = eng.stats()["perf"]["decode_step"]
    moe = step["moe"]
    assert set(moe) == {"experts_touched_share", "expert_load_max_over_mean",
                        "routed_pairs"}
    # two of four slots run: 2 tokens x top-2 of 8 experts a sparse layer
    assert moe["routed_pairs"]["p50"] == 4.0
    assert 2 / 8 <= moe["experts_touched_share"]["mean"] <= 4 / 8
    assert 2.0 <= moe["expert_load_max_over_mean"]["mean"] <= 4.0
    pre = eng.stats()["perf"]["prefill"]["moe"]
    # a prompt's padding rows are routed but are nobody's load
    assert pre["routed_pairs"]["mean"] == (9 + 30) / 2 * 2
    assert 0 < pre["experts_touched_share"]["mean"] <= 1.0


def test_window_block_share_follows_the_contexts(model, fresh_timeline):
    eng = LLMEngine(model, block_size=8, max_slots=2, max_model_len=96)
    # contexts (the written token counted) 41 and 9: window layers walk
    # the pages from (ctx - 8) // 8 on: 2 of 6 and 2 of 2
    share = eng._window_block_share(np.asarray([41, 9]))
    assert share == pytest.approx((2 + 2) / (6 + 2))
    sp = SamplingParams(max_new_tokens=6, temperature=0.0)
    eng.generate(_prompts(40), sp)
    got = eng.stats()["perf"]["decode_step"]["window_block_share"]
    # contexts 41..45: 2 of 6 pages
    assert got["mean"] == pytest.approx(2 / 6)


def test_a_model_that_counts_nothing_adds_nothing_to_the_step(fresh_timeline):
    paddle_tpu.seed(0)
    llama = LlamaForCausalLM(llama_tiny(vocab=61, hidden=32, layers=2,
                                        seq=64))
    eng = LLMEngine(llama, block_size=8, max_slots=2, max_model_len=64)
    eng.generate(_prompts(7), SamplingParams(max_new_tokens=4,
                                             temperature=0.0))
    step = eng.stats()["perf"]["decode_step"]
    assert "moe" not in step and "window_block_share" not in step
    # a prefill's block holds the engine's own count of attention paths
    assert set(eng.stats()["perf"]["prefill"]) == {"attention"}
    out = jax.eval_shape(
        eng._py_fns["decode"], eng.params, eng.buffers, eng.cache.pool,
        *(jnp.zeros((2,) + s, d) for s, d in (
            ((), jnp.int32), ((8,), jnp.int32), ((), jnp.int32),
            ((), jnp.float32), ((), jnp.int32), ((), jnp.float32),
            ((), jnp.int32), ((), jnp.int32))))
    assert out[2] is None and len(jax.tree_util.tree_leaves(out)) == 2


def test_yarn_table_against_numbers_written_out():
    """Laguna-XS.2's full-attention RoPE: 64 of 128 dims rotated, theta
    500,000, factor 64 from 4,096 positions, betas 64 and 1. The correction
    range is c(64) = 5.66 -> 5 and c(1) = 15.80 -> 16 with c(b) = 64 ln(4096
    / (2 pi b)) / (2 ln 500000): frequencies 0..5 are kept, 16..31 divided
    by 64, those between blended; cos and sin carry 0.1 ln 64 + 1."""
    p = {"rope_type": "yarn", "rope_theta": 500000, "factor": 64,
         "original_max_position_embeddings": 4096, "beta_fast": 64,
         "beta_slow": 1, "attention_factor": 1.4158883083359672,
         "partial_rotary_factor": 0.5}
    cos, sin = laguna_rope_tables(128, 1024 + 1, p, jnp.float64)
    assert cos.shape == sin.shape == (1025, 32)
    factor = 0.1 * math.log(64) + 1
    assert factor == pytest.approx(1.4158883083359672, rel=1e-12)
    inv_freq = {0: 1.0, 5: 0.12868737343265052, 6: 0.07775503023178373,
                10: 0.009150584078844943, 15: 0.00022400972405040552,
                16: 2.209708691207961e-05, 31: 4.709153362717455e-08}
    for i, f in inv_freq.items():
        assert float(cos[1, i]) == pytest.approx(factor * math.cos(f),
                                                 rel=1e-9)
        assert float(sin[1, i]) == pytest.approx(factor * math.sin(f),
                                                 rel=1e-9)
    assert float(cos[1000, 10]) == pytest.approx(-1.3629960785664534,
                                                 rel=1e-6)
    assert float(sin[1000, 10]) == pytest.approx(0.38338152210944854,
                                                 rel=1e-6)
    # the sliding layers': plain, all 128 dims, theta 10,000, no factor
    cos, sin = laguna_rope_tables(128, 8, {"rope_type": "default",
                                           "rope_theta": 10000,
                                           "partial_rotary_factor": 1},
                                  jnp.float64)
    assert cos.shape == (8, 64)
    assert float(sin[3, 1]) == pytest.approx(
        math.sin(3 * 10000 ** (-2 / 128)), rel=1e-9)
