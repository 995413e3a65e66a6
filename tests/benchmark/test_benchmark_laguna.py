"""The second architecture's files (``arch/laguna.py``,
``reference/laguna.py``, ``laguna-xs.2-serve-l5``, ``decode-closed-1k``, the
``moe_roofline`` reader): the published file against the cut, the leaves and
their pinned sums, the plain reference against the program on the CPU at a
tiny size (full forward, then prefill and decode through the paged cache
past the window), the eight shares of the experts against the uncut layer,
the work counts against numbers worked out by hand, the new reader against a
trace and a ``stats`` made by hand, and the serve driver rehearsed end to
end on the new cell with its int8 control and a planted fault."""
import io
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.lib import harness, peaks as peaks_mod, spec as spec_mod
from benchmark.lib import trace as T, weights

import _tiny

CELL, CONFIG, MIX = ("laguna-xs2-decode-closed", "laguna-xs.2-serve-l5",
                     "decode-closed-1k")
SPEC = spec_mod.Spec(_tiny.ROOT)
arch = SPEC.module("arch", "laguna")
ref = SPEC.module("reference", "laguna")
FULL = SPEC.config(CONFIG)
CFG = dict(FULL, **arch.tiny({}))
SHAPES = arch.shapes(CFG)

with open(os.path.join(os.path.dirname(__file__),
                       "recorded_weights_laguna_tiny.json")) as f:
    RECORDED = json.load(f)


@pytest.fixture(scope="module")
def w():
    return weights.make_weights(SHAPES, 2**31 + 7, "float32")


@pytest.fixture(scope="module")
def model(w):
    m = arch.build_model(CFG, 128)
    for n, p in m.named_parameters():
        assert tuple(p.shape) == SHAPES[n], n
        p._value = w[n]
    assert set(dict(m.named_parameters())) == set(SHAPES)
    return m


# -- the configuration, the cell, the leaves -----------------------------------
def test_the_file_is_the_published_one_cut_in_depth_alone():
    pub = arch.PUBLISHED[FULL["source"]]
    cut = {"num_hidden_layers", "layer_types", "mlp_layer_types",
           "num_attention_heads_per_layer"}
    assert set(FULL["reduced"]) == cut and not cut & set(arch.WIDTH_KEYS)
    for k, v in pub.items():
        assert FULL[k] == (v[:5] if isinstance(v, list) else
                           5 if k == "num_hidden_layers" else v), k
    assert (FULL["num_experts"], FULL["vocab_size"]) == (256, 100352)
    assert FULL["layer_types"].count("sliding_attention") == 3
    assert FULL["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert FULL["engine"] == SPEC.config("mistral-7b-v0.3-serve-l8")["engine"]
    assert (FULL["arch"], FULL["reference"], FULL["driver"], FULL["dtype"]) \
        == ("laguna", "laguna", "serve", "bfloat16")
    assert len(FULL["assumed"]) >= 4 and "pipeline" in FULL["deployment"]


def test_the_cell_and_its_mix_are_as_the_issue_names_them():
    cell = SPEC.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    mix = SPEC.traffic(CELL)
    assert (mix["loop"], mix["clients"], mix["first_wave_cut"]) == (
        "closed", 32, True)
    assert mix["prompt_len"] == {"kind": "lognormal", "median": 768,
                                 "sigma": 0.4, "min": 257, "max": 1536}
    assert mix["output_len"] == {"kind": "lognormal", "median": 256,
                                 "sigma": 0.4, "min": 96, "max": 512}
    # one length a prefill bucket (512 / 1024 / 2048); the warm-up asks for
    # three tokens more, so the last cannot be 2048 itself
    assert mix["warm_prompt_lens"] == [512, 1024, 1536]
    assert (mix["round"], mix["trace_seconds"]) == (16, 6)
    chk = mix["check"]
    assert (chk["pad_to"], chk["rows"], chk["requests"]) == (2048, 2, 8)
    # the longest request fits the reference's rows and the engine's table
    assert 1536 + 512 <= min(chk["pad_to"] + 1, FULL["engine"]["max_model_len"])
    e2e = {m["name"] for m in SPEC.end_to_end(CELL)}
    assert e2e == {"output_tokens_per_s", "itl_p95_ms", "setup_s"}
    names = {m["name"] for m in SPEC.per_layer(CELL)}
    assert {"moe_grouped_matmul_roofline", "moe_time_share",
            "moe_experts_touched_share", "serve_mfu", "decode_step_device_ms",
            "paged_attention_roofline", "ttft_p50_ms.closed",
            "decode_batch_occupancy", "engine_host_ms_per_step"} <= names
    assert not {"ttft_p90_ms.open", "loadgen_lag_p95_ms", "train_mfu"} & names


def test_leaves_and_bytes_at_the_published_widths():
    shapes = arch.shapes(FULL)
    assert shapes["layers.1.mlp.gate_up_proj"] == (256, 2048, 1024)
    assert shapes["layers.1.mlp.down_proj"] == (256, 512, 2048)
    assert shapes["layers.1.mlp.router.weight"] == (2048, 256)
    assert shapes["layers.0.mlp.gate_up_proj.weight"] == (2048, 16384)
    assert shapes["layers.0.self_attn.qkv_proj.weight"] == (2048, 64 * 128)
    assert shapes["layers.1.self_attn.qkv_proj.weight"] == (2048, 80 * 128)
    assert shapes["layers.1.self_attn.gate_proj.weight"] == (2048, 64)
    assert shapes["lm_head.weight"] == (2048, 100352)
    params = sum(int(np.prod(s)) for s in shapes.values())
    # 4 x 805.3 M experts, 15 M of routers and shared experts, 172 M of
    # attention, 50 M dense MLP, 411 M embedding and head
    assert params == 3_869_857_792
    assert round(2 * params / 1e9, 2) == 7.74
    # one float32 draw at a time: the largest is an expert stack's
    assert max(int(np.prod(s)) for s in shapes.values()) * 4 == 2_147_483_648


@pytest.mark.parametrize("leaf", [r[0] for r in RECORDED["leaves"]])
def test_weights_are_pinned(leaf):
    """Each leaf of the tiny configuration against sums recorded when the
    architecture was added: the same leaves in the same order and shapes,
    the same values (an expert stack is a matrix leaf like any other)."""
    assert list(SHAPES) == [r[0] for r in RECORDED["leaves"]]
    _, shape, total, squares = next(r for r in RECORDED["leaves"]
                                    if r[0] == leaf)
    a = np.asarray(weights.make_weights(SHAPES, RECORDED["seed"], "float32",
                                        [leaf])[leaf]).astype(np.float64)
    assert list(a.shape) == shape
    assert a.sum() == pytest.approx(total, rel=1e-9, abs=1e-9)
    assert (a * a).sum() == pytest.approx(squares, rel=1e-9)


# -- the reference against the program -----------------------------------------
def test_reference_agrees_with_the_program_on_a_full_forward(model, w):
    import paddle_tpu

    tok = np.random.RandomState(0).randint(0, CFG["vocab_size"], (2, 48))
    got = np.asarray(model(paddle_tpu.to_tensor(tok))._value)
    want = np.asarray(ref.logits(CFG, w, jnp.asarray(tok)))
    assert np.abs(want).max() > 0.5
    assert np.abs(got - want).max() < 2e-5


def test_prefill_then_decode_through_the_paged_cache_past_the_window(model, w):
    """A prompt of 27 (padded to 32; the window is 16) prefilled into the
    pool's blocks, then 24 decode steps through the block table, two slots
    wide with the second one idle: the logits of every step against the
    reference's full forward pass over the whole sequence."""
    from paddle_tpu.nn.layer import functional_call, functional_state
    from paddle_tpu.serving.kv_cache import PagedCacheView

    params, buffers = functional_state(model)
    windows = tuple(l.window for l in model.cache_layers())
    assert windows == (None, 16, 16, 16, None)
    bs, n_prompt, n_new, pad = 16, 27, 24, 32
    seq = np.random.RandomState(1).randint(1, CFG["vocab_size"],
                                           n_prompt + n_new)
    want = np.asarray(ref.logits(CFG, w, jnp.asarray(seq[None])))[0]
    pool = jnp.zeros((5, 9, 2, CFG["num_key_value_heads"], bs,
                      CFG["head_dim"]), jnp.float32)
    table = np.array([3, 7, 1, 5], np.int32)
    padded = np.zeros(pad, np.int32)
    padded[:n_prompt] = seq[:n_prompt]
    view = PagedCacheView(pool, jnp.asarray(table[None, :2]), None, bs,
                          windows=windows, valid_len=n_prompt)
    logits, _ = functional_call(
        model, params, buffers, jnp.asarray(padded[None]), cache=view,
        positions=jnp.arange(pad, dtype=jnp.int32)[None], training=False)
    np.testing.assert_allclose(np.asarray(logits)[0, :n_prompt],
                               want[:n_prompt], atol=2e-5)
    assert float(view.counters["moe.layers"]) == 4.0
    assert float(view.counters["moe.routed_pairs"]) == 4 * n_prompt * 2
    pool = view.pool
    bt = np.zeros((2, 4), np.int32)
    bt[0] = table

    @jax.jit
    def step(pool, tok, ctx):
        view = PagedCacheView(pool, jnp.asarray(bt), ctx, bs, windows=windows)
        logits, _ = functional_call(
            model, params, buffers, tok[:, None], cache=view,
            positions=ctx[:, None], training=False)
        return logits[:, 0], view.pool, view.counters

    for at in range(n_prompt, n_prompt + n_new):
        got, pool, counters = step(pool, jnp.asarray([seq[at], 0], jnp.int32),
                                   jnp.asarray([at, 1], jnp.int32))
        np.testing.assert_allclose(np.asarray(got)[0], want[at], atol=2e-5)
    # the idle slot's row is routed like any other and is nobody's load
    assert float(counters["moe.routed_pairs"]) == 4 * 2


@pytest.mark.parametrize("part", [
    "layers.0.self_attn.gate_proj.weight", "layers.1.mlp.router.weight",
    "layers.2.mlp.gate_up_proj", "layers.3.mlp.down_proj",
    "layers.4.mlp.shared_expert.down_proj.weight",
    "layers.0.mlp.down_proj.weight", "layers.1.self_attn.o_proj.weight",
    "sliding_window", "rope_parameters", "moe_routed_scaling_factor"])
def test_reference_is_causal_and_uses_every_part(w, part):
    tok = np.random.RandomState(2).randint(0, CFG["vocab_size"], (1, 40))
    base = np.asarray(ref.logits(CFG, w, jnp.asarray(tok)))
    cfg, w2 = dict(CFG), dict(w)
    if part in w:
        w2[part] = w[part] * 1.5
    elif part == "rope_parameters":
        cfg[part] = dict(CFG[part], full_attention=dict(
            CFG[part]["full_attention"], factor=8))
    else:
        cfg[part] = CFG[part] * 2
    assert np.abs(np.asarray(ref.logits(cfg, w2, jnp.asarray(tok)))
                  - base).max() > 1e-4
    later = tok.copy()
    later[0, 30:] = (later[0, 30:] + 1) % CFG["vocab_size"]
    again = np.asarray(ref.logits(CFG, w, jnp.asarray(later)))
    np.testing.assert_allclose(again[0, :30], base[0, :30], atol=1e-6)


def test_a_window_layer_forgets_what_left_its_window(w):
    """With the two full-attention layers' keys and values silenced, a
    position sees 3 x 15 tokens back through three window layers at most:
    a token 60 back moves nothing, one 10 back does."""
    w2 = dict(w)
    for i in (0, 4):
        w2[f"layers.{i}.self_attn.o_proj.weight"] = jnp.zeros_like(
            w[f"layers.{i}.self_attn.o_proj.weight"])
    tok = np.random.RandomState(3).randint(0, CFG["vocab_size"], (1, 80))
    base = np.asarray(ref.logits(CFG, w2, jnp.asarray(tok)))[0, -1]
    for back, moves in ((60, False), (10, True)):
        other = tok.copy()
        other[0, -1 - back] += 1
        got = np.asarray(ref.logits(CFG, w2, jnp.asarray(other)))[0, -1]
        assert (np.abs(got - base).max() > 1e-6) == moves


def test_int8_control_is_close_but_not_equal(w):
    tok = np.random.RandomState(4).randint(0, CFG["vocab_size"], (2, 32))
    exact = np.asarray(ref.logits(CFG, w, jnp.asarray(tok)))
    low = np.asarray(ref.logits(CFG, w, jnp.asarray(tok), ref.int8_linear))
    gap = np.abs(exact - low).max()
    assert 1e-4 < gap < 0.5 * np.abs(exact).max()


def test_eight_shares_of_the_experts_add_up_to_the_uncut_layer():
    """The guide's share test, at 64 experts of which each of eight chips
    holds 8: the program's layer told ``experts_held``, the shared expert
    given to one share alone, against the reference's uncut layer."""
    import paddle_tpu
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models.laguna import LagunaConfig, LagunaSparseMLP

    cfg = dict(CFG, num_experts=64, num_experts_per_tok=8)
    lw = weights.make_weights(
        {n[len("layers.1."):]: s for n, s in arch.shapes(cfg).items()
         if n.startswith("layers.1.mlp.")}, 2**31 + 9, "float32")
    x = jnp.asarray(np.random.RandomState(5).randn(2, 9, cfg["hidden_size"]),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.sparse_mlp(cfg, lw, x, ref.f32_linear))
    total = 0
    for chip in range(8):
        layer = LagunaSparseMLP(LagunaConfig(
            hidden_size=cfg["hidden_size"], num_experts=64,
            num_experts_per_tok=8,
            moe_intermediate_size=cfg["moe_intermediate_size"],
            shared_expert_intermediate_size=cfg[
                "shared_expert_intermediate_size"],
            num_hidden_layers=1, experts_held=(8 * chip, 8)))
        for n, p in layer.named_parameters():
            a = lw["mlp." + n]
            p._value = a[8 * chip:8 * chip + 8] if a.ndim == 3 else a
        if chip:                     # the shared expert is counted once
            del layer._sub_layers["shared_expert"]
        total = total + layer(Tensor(x))._value
    assert np.abs(want).max() > 0.01
    np.testing.assert_allclose(np.asarray(total), want, atol=1e-5)


# -- the work counts -------------------------------------------------------------
H, D = 2048, 128
ATTN48 = H * 48 * D + 2 * H * 8 * D + H * 48 + 48 * D * H
ATTN64 = H * 64 * D + 2 * H * 8 * D + H * 64 + 64 * D * H
EXPERT = 3 * H * 512
SPARSE = H * 256 + 9 * EXPERT


@pytest.mark.parametrize("what,got,want", [
    ("dense layer", lambda: arch.layer_matmul_params(FULL, 0),
     ATTN48 + 3 * H * 8192),
    ("window layer", lambda: arch.layer_matmul_params(FULL, 1),
     ATTN64 + SPARSE),
    ("full sparse layer", lambda: arch.layer_matmul_params(FULL, 4),
     ATTN48 + SPARSE),
    ("pairs, no window", lambda: arch.seen_pairs(1000, None), 500500),
    ("pairs, inside the window", lambda: arch.seen_pairs(512, 512), 131328),
    ("pairs, past the window", lambda: arch.seen_pairs(1000, 512),
     131328 + 488 * 512),
    ("decode at 1000", lambda: arch.decode_flops(FULL, 1000),
     2 * (2 * ATTN48 + 3 * ATTN64 + 3 * H * 8192 + 4 * SPARSE + H * 100352)
     + 4 * D * (2 * 48 * 1000 + 3 * 64 * 512)),
    ("decode at 300", lambda: arch.decode_flops(FULL, 300)
     - arch.decode_flops(FULL, 299), 4 * D * (2 * 48 + 3 * 64)),
    ("prefill of 1000", lambda: arch.prefill_flops(FULL, 1000),
     2000 * (2 * ATTN48 + 3 * ATTN64 + 3 * H * 8192 + 4 * SPARSE)
     + 4 * D * (2 * 48 * 500500 + 3 * 64 * (131328 + 488 * 512))
     + 2 * H * 100352),
    ("sparse layers", lambda: arch.sparse_layers(FULL), 4),
])
def test_counts(what, got, want):
    assert got() == want, what


def test_paged_attention_work_caps_a_window_layers_bytes_at_the_window():
    got = arch.paged_attention_decode(FULL, [100, 1000])
    kv = 2 * 8 * D * 2                      # K and V of a position, bf16
    seen_full, seen_window = 1100, 100 + 512
    assert got["bytes"] == (2 * seen_full + 3 * seen_window) * kv + (
        2 * 2 * D * 2 * (2 * 48 + 3 * 64))
    assert got["flops"] == 4 * D * (2 * 48 * seen_full + 3 * 64 * seen_window)


def test_moe_work_follows_the_experts_that_were_read():
    got = arch.moe_experts(FULL, pairs=256, experts_read=160)
    assert got["flops"] == 2 * 256 * EXPERT
    assert got["bytes"] == 2 * (160 * EXPERT + 256 * (H + 1024 + 512 + H))
    # decode is bound by the weights' bytes on a v5e by two orders
    peak = peaks_mod.peaks("TPU v5 lite")
    assert (got["bytes"] / peak["hbm_bytes_per_s"]
            > 50 * got["flops"] / peak["bf16_flops_per_s"])


# -- the new reader ----------------------------------------------------------------
DEV = "/device:TPU:0"


def _facts(**over):
    tr = T.Trace.from_json({
        "modules": {DEV: [["jit_decode(1)", 0, 4000], ["jit_prefill(2)", 4000,
                                                       9000],
                          ["jit_decode(1)", 13000, 4000]]},
        "ops": {DEV: [["moe_grouped_matmul.3", 100, 1000],
                      ["moe_grouped_matmul.4", 5000, 2000],
                      ["fusion.1", 7000, 500]]},
        "host": [], "window": [0, 20000]})
    stats = {"perf": {
        "decode_step": {"moe": {"experts_touched_share": {"mean": 0.625}}},
        "prefill": {"moe": {"experts_touched_share": {"mean": 1.0}}}}}
    facts = dict(trace=tr, peaks=peaks_mod.peaks("TPU v5 lite"), cfg=FULL,
                 arch=arch, chips=1, stats=stats,
                 decode_contexts=[800] * 60, prefill_lens=[700])
    facts.update(over)
    return facts


def test_moe_roofline_reads_the_programs_counter_and_the_trace():
    reader = SPEC.module("readers", "moe_roofline")
    args = SPEC.load_json("metrics", "moe_grouped_matmul_roofline")["args"]
    got = reader.read(_facts(), **args)
    # two decode programs at 0.625 of 256 experts, one prefill at all of
    # them, four sparse layers; 60 + 700 tokens' pairs
    need = arch.moe_experts(FULL, (60 + 700) * 8 * 4,
                            (2 * 0.625 + 1.0) * 256 * 4)
    least = need["bytes"] / 819e9
    assert least > need["flops"] / 197e12
    assert got == pytest.approx(100 * least / 3000e-9)
    # a parent without the counters, a CPU without peaks, a trace without
    # the kernel: nothing to read
    assert reader.read(_facts(stats={"perf": {"decode_step": {}}}),
                       **args) is None
    assert reader.read(_facts(peaks=None), **args) is None
    assert reader.read(_facts(trace=None), **args) is None
    llama = SPEC.module("arch", "llama")
    assert reader.read(_facts(arch=llama), **args) is None
    # the other two are the generic readers'
    assert SPEC.load_json("metrics", "moe_time_share")["reader"] == "time_share"
    share = SPEC.module("readers", "time_share").read(
        _facts(), **SPEC.load_json("metrics", "moe_time_share")["args"])
    assert share == pytest.approx(100 * 3000 / 3500)
    touched = SPEC.module("readers", "program_counter").read(
        _facts(), **SPEC.load_json("metrics",
                                   "moe_experts_touched_share")["args"])
    assert touched == pytest.approx(62.5)


# -- the serve driver on the new cell ----------------------------------------------
@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = _tiny.make_root(tmp_path_factory.mktemp("bench") / "root")

    def cut(doc):       # as _tiny cuts decode-closed
        doc.update(clients=4, round=8, warm_prompt_lens=[16, 32, 64],
                   trace_seconds=2,
                   prompt_len={"kind": "lognormal", "median": 24,
                               "sigma": 0.5, "min": 10, "max": 48},
                   output_len={"kind": "lognormal", "median": 12,
                               "sigma": 0.4, "min": 6, "max": 20})
        doc["check"].update(requests=64, pad_to=128, rows=8)
        doc["check"]["limits"] = {"logit_gap_max": 2e-3,
                                  "logit_gap_mean": 2e-5,
                                  "compared_tokens_min": 10}

    _tiny._edit(os.path.join(root, "benchmark", "traffic", MIX + ".json"), cut)
    return root


@pytest.fixture
def pallas_interpret(uninstall_mesh):
    from paddle_tpu import kernels

    kernels.set_use_pallas(True)
    yield
    kernels.set_use_pallas(None)


def _run(root, seconds, trace, **kw):
    out = io.StringIO()
    rc, result = harness.run_cell(CELL, 2**31 + 77, seconds, trace, root=root,
                                  require_chip=False, out=out, **kw)
    assert json.loads(out.getvalue().strip().splitlines()[-1]) == \
        json.loads(json.dumps(result))
    return rc, result


def test_the_rehearsal_cuts_the_configuration_by_its_own_architecture(root):
    small = spec_mod.Spec(root).config(CONFIG)
    assert (small["hidden_size"], small["num_experts"],
            small["sliding_window"]) == (128, 8, 16)
    assert small["num_hidden_layers"] == 5 and small["dtype"] == "float32"
    assert small["engine"]["max_slots"] == 4


def test_closed_loop_run_with_its_control(root, pallas_interpret):
    """Contexts of up to 68 through windows of 16, the sparse layers through
    the grouped kernel in interpret mode, the whole served stack."""
    rc, res = _run(root, 3.0, False, control=True)
    assert rc == 0 and res["correct"] is True
    assert set(res["metrics"]) == {"output_tokens_per_s", "itl_p95_ms",
                                   "setup_s"}
    assert res["attempted"] > 4 and res["failed"] == 0
    chk = res["checks"]
    assert chk["compiles_in_window"] == {"value": 0, "limit": 0}
    assert chk["wrong_length"]["value"] == 0
    # the program (float32 here) is inside the limit, the int8 control is not
    for k in ("logit_gap_max", "logit_gap_mean"):
        assert chk[k]["value"] <= chk[k]["limit"] < res["control"][k + ".int8"]


def test_a_traced_run_reads_the_programs_counters_with_a_stand_in_peaks(
        root, pallas_interpret, monkeypatch):
    real = harness.read_per_layer

    def as_on_a_chip(ctx, run, device):
        ctx.require_chip = True
        return real(ctx, run, device)

    monkeypatch.setattr(harness, "read_per_layer", as_on_a_chip)
    monkeypatch.setattr(peaks_mod, "peaks", lambda kind: {
        "bf16_flops_per_s": 1e12, "int8_ops_per_s": 2e12,
        "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9, "source": "a stand-in"})
    rc, res = _run(root, 3.0, True)
    assert rc == 0 and res["correct"] is True
    got = {k: v["value"] for k, v in res["metrics"].items()}
    # 2 of 8 experts a token, at most 4 tokens a step
    assert 25.0 <= got["moe_experts_touched_share"] <= 100.0
    assert res["metrics"]["moe_experts_touched_share"]["unit"] == "%"
    assert got["decode_batch_occupancy"] <= 100.0 and got["serve_mfu"] > 0
    # no TPU plane in the trace: no kernel to take a share of
    assert not {"moe_grouped_matmul_roofline", "moe_time_share",
                "paged_attention_roofline"} & set(got)


@pytest.mark.parametrize("fault", ["token", "window", "shared_expert"])
def test_a_planted_fault_is_not_correct(root, pallas_interpret, monkeypatch,
                                        fault):
    """A token altered where it is produced; a window layer that sees the
    whole context; a sparse layer without its shared expert."""
    if fault == "token":
        from paddle_tpu.serving import engine as engine_mod

        real = engine_mod.sample_logits
        monkeypatch.setattr(
            engine_mod, "sample_logits", lambda logits, *a, **kw:
            (real(logits, *a, **kw) + 1) % logits.shape[-1])
    elif fault == "window":
        from paddle_tpu.serving.kv_cache import PagedCacheView

        monkeypatch.setattr(PagedCacheView, "_window", lambda self, i: None)
    else:
        from paddle_tpu.distributed.moe import SparseMoELayer

        real_forward = SparseMoELayer.forward

        def without_shared(self, x, row_mask=None):
            sub = self._sub_layers.pop("shared_expert")
            try:
                return real_forward(self, x, row_mask)
            finally:
                self._sub_layers["shared_expert"] = sub

        monkeypatch.setattr(SparseMoELayer, "forward", without_shared)
    rc, res = _run(root, 2.0, False)
    assert res["correct"] is False
    assert any(res["checks"][k]["value"] > res["checks"][k]["limit"]
               for k in ("logit_gap_max", "logit_gap_mean"))
