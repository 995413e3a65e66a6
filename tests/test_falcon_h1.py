"""``models/falcon_h1.py`` and the engine's state cache on the CPU at a small
size (hidden 128, 2 blocks, 4 mixer heads of 16, state 16, 2 groups, chunk
8, vocabulary 512), float32, on weights drawn so that the carried state is
most of the mixer's output (with random weights of the usual kind the skip
term hides the recurrence: PERF.md §4): the program's model against the
plain reference, the reference against the publisher's code, the engine's
prefill and decode through both caches against the reference at every
served position, the kernels against the sequential recurrence, planted
faults of the state's carry, padding, precision and mathematics, slots
handed over and preempted, and prefix reuse switched off."""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu
from benchmark.lib import spec as spec_mod
from paddle_tpu.models import (FalconH1Config, FalconH1ForCausalLM,
                               LlamaForCausalLM, llama_tiny)
from paddle_tpu.serving import LLMEngine, SamplingParams
from paddle_tpu.serving import engine as engine_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPEC = spec_mod.Spec(ROOT)
arch = SPEC.module("arch", "falcon_h1")
ref = SPEC.module("reference", "falcon_h1")
PUBLISHED = next(iter(arch.PUBLISHED.values()))
CFG = dict(PUBLISHED, vocab_size=512, hidden_size=128, intermediate_size=256,
           num_hidden_layers=2, num_attention_heads=10,
           num_key_value_heads=2, head_dim=16, mamba_d_ssm=64,
           mamba_n_heads=4, mamba_d_head=16, mamba_d_state=16,
           mamba_n_groups=2, mamba_chunk_size=8, max_position_embeddings=768)
SHAPES = arch.shapes(CFG)
TOL = 2e-5
LENGTHS = (1, 3, 7, 8, 9, 17, 127, 128, 129, 200)
N_NEW = 40


def draw(d_skip, seed=0):
    """Weights under which the recurrence matters: steps of 1e-3 to 1e-1,
    decays ``A`` of 1 to 16 (a head remembers tens to hundreds of tokens),
    conv taps uniform in +-0.5, B and C of order one, ``D`` as given; unit
    embeddings and a head that makes logits of order one."""
    rng = np.random.RandomState(seed)
    w = {}
    d, gn, hm = CFG["mamba_d_ssm"], 32, CFG["mamba_n_heads"]
    for name, shape in SHAPES.items():
        leaf = name.rsplit(".", 2)[-2:]
        if len(shape) == 1:
            a = 1.0 + 0.1 * rng.randn(*shape)
        else:
            a = rng.randn(*shape) / np.sqrt(shape[0])
        if name == "embed_tokens.weight":
            a = rng.randn(*shape) / CFG["embedding_multiplier"]
        elif name == "lm_head.weight":
            a = rng.randn(*shape) * 12.0
        elif leaf == ["in_proj", "weight"]:
            a[:, d:2 * d] *= 4.0                        # x
            a[:, 2 * d:2 * d + 2 * gn] *= 100.0         # B and C
            a[:, 2 * d + 2 * gn:] *= 0.5                # dt
        elif leaf == ["out_proj", "weight"] and "mamba" in name:
            a *= 4.0
        elif leaf == ["conv1d", "weight"]:
            a = rng.uniform(-0.5, 0.5, shape)
        elif leaf == ["conv1d", "bias"]:
            a = 0.1 * rng.randn(*shape)
        elif name.endswith("A_log"):
            a = np.log(rng.uniform(1.0, 16.0, hm))
        elif name.endswith("dt_bias"):
            step = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), hm))
            a = np.log(np.expm1(step))
        elif name.endswith("mamba.D"):
            a = np.full(hm, float(d_skip))
        w[name] = jnp.asarray(a, jnp.float32)
    return w


def build(w, **config):
    model = FalconH1ForCausalLM(FalconH1Config.from_dict(dict(CFG, **config)))
    params = dict(model.named_parameters())
    assert set(params) == set(SHAPES)
    for n, p in params.items():
        assert tuple(p.shape) == SHAPES[n], n
        p._value = w[n]
    return model


@pytest.fixture(scope="module", params=[0, 1], ids=["D0", "D1"])
def weights(request):
    return draw(request.param)


@pytest.fixture(scope="module")
def model(weights):
    return build(weights)


@pytest.fixture(scope="module")
def skip_weights():
    """``D`` = 1 alone, the harder case (the skip term beside the state),
    for what need not run twice."""
    return draw(1)


@pytest.fixture(scope="module")
def skip_model(skip_weights):
    return build(skip_weights)


def _sequence(length, seed=0):
    return np.random.RandomState([seed, length]).randint(
        1, CFG["vocab_size"], length)


def _ref_logits(w, seq, cfg=CFG):
    return np.asarray(ref.forward(cfg, w, jnp.asarray(seq)[None]))[0]


# -- the weights make the test mean something ----------------------------------
def test_the_carried_state_is_most_of_the_mixers_output(weights):
    u = jnp.asarray(np.random.RandomState(1).randn(1, 96, 128), jnp.float32)
    lw = ref.layer_weights(weights, 0)
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(ref.mixer(CFG, lw, u, ref.f32_linear))
        forgetful = np.asarray(ref.mixer(CFG, lw, u, ref.f32_linear,
                                         carry=False))
    assert (np.linalg.norm(whole - forgetful)
            > 0.5 * np.linalg.norm(whole)), "the skip term hides the state"


# -- (1) the program's model against the reference -----------------------------
def test_the_model_agrees_with_the_reference_on_a_full_forward(model, weights):
    tok = np.stack([_sequence(61, 2), _sequence(61, 3)])
    got = np.asarray(model(paddle_tpu.to_tensor(tok))._value)
    want = np.asarray(ref.forward(CFG, weights, jnp.asarray(tok)))
    assert np.abs(want).max() > 0.5
    assert np.abs(got - want).max() < TOL


def test_cache_layers_declare_both_caches():
    from paddle_tpu.serving import CacheLayer, StateLayer

    model = build(draw(1))
    layers = model.cache_layers()
    assert layers == [CacheLayer(2, 16, None),
                      StateLayer(((4, 16, 16), "float32"),
                                 ((3, 64 + 2 * 2 * 16), None))] * 2
    c = FalconH1Config()
    assert (c.num_hidden_layers, c.head_dim, c.conv_dim) == (72, 128, 5120)
    assert c.hidden_size // c.num_attention_heads == 256   # not the head's
    with pytest.raises(ValueError, match="mamba_d_ssm"):
        FalconH1Config(mamba_n_heads=31)


# -- (2) the reference against the publisher's code ----------------------------
def test_the_reference_agrees_with_the_publishers_code(weights):
    torch = pytest.importorskip("torch")
    hf = pytest.importorskip("transformers.models.falcon_h1")
    keys = {k: v for k, v in CFG.items() if k != "model_type"}
    their = hf.FalconH1ForCausalLM(hf.FalconH1Config(
        **keys, attn_implementation="eager")).eval().float()
    w = {n: np.asarray(a) for n, a in weights.items()}
    sd = {"model.embed_tokens.weight": w["embed_tokens.weight"],
          "model.final_layernorm.weight": w["final_layernorm.weight"],
          "lm_head.weight": w["lm_head.weight"].T}
    nq, nkv = 10 * 16, 2 * 16
    for i in range(CFG["num_hidden_layers"]):
        p, q = f"layers.{i}.", f"model.layers.{i}."
        qkv = w[p + "self_attn.qkv_proj.weight"]
        gate_up = w[p + "feed_forward.gate_up_proj.weight"]
        sd.update({
            q + "self_attn.q_proj.weight": qkv[:, :nq].T,
            q + "self_attn.k_proj.weight": qkv[:, nq:nq + nkv].T,
            q + "self_attn.v_proj.weight": qkv[:, nq + nkv:].T,
            q + "self_attn.o_proj.weight": w[p + "self_attn.o_proj.weight"].T,
            q + "mamba.in_proj.weight": w[p + "mamba.in_proj.weight"].T,
            q + "mamba.conv1d.weight": w[p + "mamba.conv1d.weight"][:, None],
            q + "mamba.out_proj.weight": w[p + "mamba.out_proj.weight"].T,
            q + "feed_forward.gate_proj.weight": gate_up[:, :256].T,
            q + "feed_forward.up_proj.weight": gate_up[:, 256:].T,
            q + "feed_forward.down_proj.weight":
                w[p + "feed_forward.down_proj.weight"].T})
        for leaf in ("mamba.conv1d.bias", "mamba.A_log", "mamba.D",
                     "mamba.dt_bias", "mamba.norm.weight",
                     "input_layernorm.weight", "pre_ff_layernorm.weight"):
            sd[q + leaf] = w[p + leaf]
    missing, unexpected = their.load_state_dict(
        {k: torch.tensor(np.ascontiguousarray(v)) for k, v in sd.items()},
        strict=False)
    assert not unexpected and not [k for k in missing if "mup" not in k]
    tok = np.stack([_sequence(45, 4), _sequence(45, 5)])
    with torch.no_grad():
        theirs = their(torch.tensor(tok), logits_to_keep=0).logits.numpy()
    want = np.asarray(ref.forward(CFG, weights, jnp.asarray(tok)))
    assert np.abs(want).max() > 0.5
    assert np.abs(theirs - want).max() < 1e-4


# -- (3) the engine through both caches against the reference ------------------
class Tap:
    """The logits the engine samples from, as the jitted steps produce
    them: ``sample_logits`` wrapped with a host callback. A step keeps the
    wrapper it was traced with, so a tap lives as long as its engine."""

    def __init__(self, monkeypatch):
        self.seen = []
        real = engine_mod.sample_logits

        def tapped(logits, *a, **kw):
            jax.debug.callback(
                lambda x: self.seen.append(np.asarray(x)), logits)
            return real(logits, *a, **kw)

        monkeypatch.setattr(engine_mod, "sample_logits", tapped)

    def take(self):
        jax.effects_barrier()
        out, self.seen = self.seen, []
        return out


def serve(eng, tap, prompt, n_new):
    """One request alone through ``eng``: its tokens and the logits of
    every position it was served at (the prefill's, then each step's)."""
    tap.take()
    req = eng.add_request([int(t) for t in prompt],
                          SamplingParams(max_new_tokens=n_new))
    rows = []
    while not req.state.is_terminal:
        slot = next((s for s, r in eng.scheduler.running.items()
                     if r is req), None)
        eng.step()
        for logits in tap.take():
            if logits.ndim == 1:
                rows.append(logits)
            else:
                slot = next((s for s, r in eng.scheduler.running.items()
                             if r is req), slot)
                rows.append(logits[slot])
    assert req.state.value == "finished", req.error
    return list(req.output_tokens), np.stack(rows)


def worst_gap(eng, tap, w, length, n_new=N_NEW, cfg=CFG):
    prompt = _sequence(length)
    out, got = serve(eng, tap, prompt, n_new)
    assert got.shape[0] == n_new
    want = _ref_logits(w, np.concatenate([prompt, out[:-1]]), cfg)
    return np.abs(got - want[length - 1:]).max()


@pytest.fixture(scope="module")
def engine(model):
    with pytest.MonkeyPatch.context() as patch:
        tap = Tap(patch)
        eng = LLMEngine(model, block_size=16, max_slots=2, max_model_len=256)
        yield eng, tap
        eng.close()


@pytest.mark.parametrize("length", LENGTHS)
def test_prefill_then_decode_agrees_with_the_reference_at_every_position(
        engine, weights, length):
    """Prompt lengths that are no multiple of the chunk (8), the block (16)
    or the bucket, then 40 decode steps: the state after the last valid
    token, the conv's last valid inputs, the recurrence token by token."""
    eng, tap = engine
    assert worst_gap(eng, tap, weights, length) < TOL
    stats = eng.stats()
    assert stats["prefix_cache"]["hits"] == 0
    assert stats["state_cache"]["prefix_reuse"] == "off: state layers"


# -- (4) the kernels -------------------------------------------------------------
def _scan_inputs(t, h, p, g, n, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    f32 = jnp.float32
    return (jax.random.normal(k[0], (t, h, p), f32),
            jax.nn.softplus(jax.random.normal(k[1], (t, h), f32) - 2.0),
            -jnp.exp(jax.random.normal(k[2], (h,), f32)),
            jax.random.normal(k[3], (t, g, n), f32),
            jax.random.normal(k[4], (t, g, n), f32))


def _sequential(x, dt, a, b, c):
    """The recurrence token by token, through the reference."""
    rep = x.shape[1] // b.shape[1]
    y = ref.recurrence(x[None], dt[None], a, jnp.repeat(b, rep, 1)[None],
                       jnp.repeat(c, rep, 1)[None])
    return np.asarray(y[0])


@pytest.mark.parametrize("shape", [(37, 4, 16, 2, 16, 8), (256, 4, 16, 2, 16, 8),
                                   (200, 8, 32, 2, 64, 32), (5, 2, 8, 1, 8, 16)])
def test_chunked_scan_is_the_sequential_recurrence(shape):
    from paddle_tpu.kernels.ssd_chunk_scan import (ssd_chunk_scan_pallas,
                                                   ssd_chunk_scan_ref)

    *dims, chunk = shape
    args = _scan_inputs(*dims)
    want = _sequential(*args)
    y, state = ssd_chunk_scan_ref(*args, chunk=chunk)
    scale = np.abs(want).max()
    assert np.abs(np.asarray(y) - want).max() < 1e-5 * scale
    yk, sk = ssd_chunk_scan_pallas(*args, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(y), atol=1e-5 * scale)
    np.testing.assert_allclose(np.asarray(sk), np.asarray(state), atol=1e-5)
    # positions whose dt is 0 leave the state as it is
    x, dt, a, b, c = args
    cut = dims[0] // 2 + 1
    _, padded = ssd_chunk_scan_ref(
        x, dt.at[cut:].set(0.0), a, b, c, chunk=chunk)
    _, short = ssd_chunk_scan_ref(x[:cut], dt[:cut], a, b[:cut], c[:cut],
                                  chunk=chunk)
    np.testing.assert_allclose(np.asarray(padded), np.asarray(short), atol=1e-6)


@pytest.mark.parametrize("shape", [(5, 4, 16, 2, 16), (3, 8, 32, 2, 64)])
def test_state_update_kernel_is_its_fallback_and_one_step_of_the_scan(shape):
    from paddle_tpu.kernels.ssd_chunk_scan import ssd_chunk_scan_ref
    from paddle_tpu.kernels.ssm_state_update import (ssm_state_update_pallas,
                                                     ssm_state_update_ref)

    s, h, p, g, n = shape
    x, dt, a, b, c = _scan_inputs(s, h, p, g, n, seed=1)
    state = jax.random.normal(jax.random.PRNGKey(9), (3, s, h, n, p),
                              jnp.float32)
    y, new = ssm_state_update_ref(state, 1, x, dt, a, b, c)
    yk, newk = ssm_state_update_pallas(state, 1, x, dt, a, b, c,
                                       interpret=True)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(y), atol=1e-5)
    np.testing.assert_allclose(np.asarray(newk), np.asarray(new), atol=1e-6)
    # the other layers' rows are untouched
    assert (np.asarray(newk)[[0, 2]] == np.asarray(state)[[0, 2]]).all()
    # from a zero state, one step is a one-token scan
    zero = jnp.zeros_like(state)
    y0, new0 = ssm_state_update_ref(zero, 0, x, dt, a, b, c)
    for row in range(s):
        y1, s1 = ssd_chunk_scan_ref(x[row:row + 1], dt[row:row + 1], a,
                                    b[row:row + 1], c[row:row + 1], chunk=8)
        np.testing.assert_allclose(np.asarray(y0[row]), np.asarray(y1[0]),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(new0[0, row]), np.asarray(s1),
                                   atol=1e-6)


# -- (5) planted faults ------------------------------------------------------------
def _zero_state_handed_to_decode(monkeypatch):
    from paddle_tpu.kernels import ssd_chunk_scan as mod
    real = mod.ssd_chunk_scan

    def forgetful(*a, **kw):
        y, state = real(*a, **kw)
        return y, jnp.zeros_like(state)

    monkeypatch.setattr(mod, "ssd_chunk_scan", forgetful)


def _padding_updates_the_state(monkeypatch):
    from paddle_tpu.serving.kv_cache import PagedCacheView

    monkeypatch.setattr(PagedCacheView, "live_rows",
                        lambda self, shape: jnp.ones(shape, bool))


def _conv_state_from_padded_positions(monkeypatch):
    from paddle_tpu.serving.kv_cache import PagedCacheView
    real = PagedCacheView.shift

    def shift(self, layer_idx, u):
        valid, self.valid_len = self.valid_len, (
            self.valid_len if self.valid_len is None else u.shape[1])
        try:
            return real(self, layer_idx, u)
        finally:
            self.valid_len = valid

    monkeypatch.setattr(PagedCacheView, "shift", shift)


def _state_in_bf16(monkeypatch):
    real = FalconH1ForCausalLM.cache_layers

    def low(self):
        return [l if not hasattr(l, "state")
                else l._replace(state=(l.state[0], "bfloat16"))
                for l in real(self)]

    monkeypatch.setattr(FalconH1ForCausalLM, "cache_layers", low)


def _group_norm_over_all_channels(monkeypatch):
    from paddle_tpu.models.falcon_h1 import FalconH1Mixer

    def whole(self, gated):
        var = jnp.mean(jnp.square(gated), axis=-1, keepdims=True)
        return gated * jax.lax.rsqrt(var + self.config.rms_norm_eps)

    monkeypatch.setattr(FalconH1Mixer, "_group_norm", whole)


FAULTS = {
    "zero state handed to decode": (_zero_state_handed_to_decode, {}),
    "padding updates the state": (_padding_updates_the_state, {}),
    "conv state from padded positions": (_conv_state_from_padded_positions,
                                         {}),
    "state in bf16": (_state_in_bf16, {}),
    "ssm_out_multiplier dropped": (None, {"ssm_out_multiplier": 1.0}),
    "key_multiplier dropped": (None, {"key_multiplier": 1.0}),
    "ssm_multipliers dropped": (None, {"ssm_multipliers": [1.0] * 5}),
    "group norm over all channels": (_group_norm_over_all_channels, {}),
}


@pytest.mark.parametrize("fault", [None, *FAULTS])
def test_each_planted_fault_is_seen(skip_weights, monkeypatch, fault):
    """(3) on one padded prompt (19 of 32) and 12 decode steps, on a fresh
    engine: without a fault inside the tolerance, with each outside it, so
    the tolerance holds the carry, the padding and float32."""
    plant, config = FAULTS.get(fault, (None, {}))
    if plant is not None:
        plant(monkeypatch)
    eng = LLMEngine(build(skip_weights, **config), block_size=16,
                    max_slots=2, max_model_len=64)
    gap = worst_gap(eng, Tap(monkeypatch), skip_weights, 19, n_new=12)
    eng.close()
    assert (gap < TOL) if fault is None else (gap > 2 * TOL), gap


# -- (6) slots ---------------------------------------------------------------------
@pytest.fixture(scope="module")
def alone(skip_model):
    eng = LLMEngine(skip_model, block_size=16, max_slots=8, max_model_len=256)
    prompt = [int(t) for t in _sequence(41, 7)]
    out = eng.generate([prompt], SamplingParams(max_new_tokens=24))[0]
    yield eng, prompt, out
    eng.close()


def test_a_request_beside_seven_others_gives_the_tokens_it_gives_alone(alone):
    eng, prompt, want = alone
    others = [[int(t) for t in _sequence(n, 8)]
              for n in (5, 23, 64, 90, 17, 33, 120)]
    sp = SamplingParams(max_new_tokens=24)
    outs = eng.generate(others[:3] + [prompt] + others[3:], sp)
    assert outs[3] == want
    assert eng.stats()["perf"]["decode_step"]["occupancy"]["mean"] > 0.5


def test_a_slot_a_longer_request_just_left_hands_nothing_over(skip_model,
                                                              alone):
    _, prompt, want = alone
    eng = LLMEngine(skip_model, block_size=16, max_slots=1, max_model_len=256)
    long = [int(t) for t in _sequence(150, 9)]
    first = eng.add_request(long, SamplingParams(max_new_tokens=30))
    second = eng.add_request(prompt, SamplingParams(max_new_tokens=24))
    eng.run()
    assert first.state.value == second.state.value == "finished"
    assert list(second.output_tokens) == want
    eng.close()


def test_a_preempted_request_rebuilds_its_state(skip_model):
    model = skip_model
    """A pool too small for three growing sequences: a request is
    preempted, queued again and prefilled again over its prompt and what it
    had produced; the state it decodes on from is that prefill's."""
    prompts = [[int(t) for t in _sequence(n, 10)] for n in (37, 30, 41)]
    sp = SamplingParams(max_new_tokens=40)
    roomy = LLMEngine(model, block_size=8, max_slots=3, max_model_len=96)
    want = roomy.generate(prompts, sp)
    roomy.close()
    eng = LLMEngine(model, block_size=8, num_blocks=20, max_slots=3,
                    max_model_len=96)
    got = eng.generate(prompts, sp)
    assert eng.stats()["num_preemptions"] > 0
    eng.close()
    assert got == want


# -- (7) prefix reuse --------------------------------------------------------------
def test_a_shared_prefix_is_prefilled_again_and_a_llama_engine_still_hits(
        skip_model):
    model = skip_model
    shared = [int(t) for t in _sequence(512, 11)]
    tails = [[int(t) for t in _sequence(n, 12)] for n in (21, 40)]
    sp = SamplingParams(max_new_tokens=16)
    each = []
    for tail in tails:
        eng = LLMEngine(model, block_size=16, max_slots=2, max_model_len=640)
        each.append(eng.generate([shared + tail], sp)[0])
        eng.close()
    eng = LLMEngine(model, block_size=16, max_slots=2, max_model_len=640)
    first = eng.generate([shared + tails[0]], sp)[0]
    second = eng.generate([shared + tails[1]], sp)[0]
    stats = eng.stats()
    assert [first, second] == each
    assert stats["prefix_cache"]["hits"] == 0
    assert stats["prefix_cache"]["enabled"] is False
    assert stats["state_cache"] == {
        "slots": 2, "bytes": eng.cache.state_nbytes,
        "bytes_per_slot": 2 * (4 * 16 * 16 + 3 * 128) * 4,
        "prefix_reuse": "off: state layers"}
    # the monitor is the process's: every open engine's state is in its tag
    assert stats["perf"]["memory"]["tags"]["state_pool"]["live_bytes"] >= \
        eng.cache.state_nbytes
    state = stats["perf"]["decode_step"]["state"]
    assert state["bytes_moved"]["mean"] == 2 * stats["state_cache"][
        "bytes_per_slot"]
    assert 0.0 < state["share_of_cache_bytes"]["mean"] < 1.0
    with pytest.raises(ValueError, match="state layers"):
        eng.export_kv_frames(["0" * 64])
    with pytest.raises(ValueError, match="state layers"):
        eng.ingest_kv_frames([])
    eng.close()
    # the same traffic on a model without state layers: the second prompt
    # hits the first's blocks, and nothing of the state cache shows
    paddle_tpu.seed(3)
    llama = LLMEngine(LlamaForCausalLM(llama_tiny(vocab=512, seq=640)),
                      block_size=16, max_slots=2, max_model_len=640)
    llama.generate([shared + tails[0]], sp)
    llama.generate([shared + tails[1]], sp)
    stats = llama.stats()
    assert stats["prefix_cache"]["hits"] == 1
    assert stats["prefix_cache"]["tokens_saved"] == 512
    assert "state_cache" not in stats
    assert "state" not in stats["perf"]["decode_step"]
    assert llama.cache.state is None
    llama.close()
