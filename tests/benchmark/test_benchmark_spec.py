"""BENCHMARK.json against the contract, and discovery by name: a later PR
adds a configuration, a mix, a metric, a reader and a whole architecture as
new files and entries and edits no file that is there."""
import json
import os
import re

import pytest

from benchmark.lib import harness, spec as spec_mod

import _tiny

ROOT = _tiny.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def doc():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys_and_sizes(doc):
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(doc["run_seconds"], int) and 1 <= doc["run_seconds"] <= 51
    assert 1 <= len(doc["paths"]) <= 16 and len(doc["command"]) <= 32
    assert 1 <= len(doc["configs"]) <= 24 and 1 <= len(doc["workloads"]) <= 24
    assert 1 <= len(doc["end_to_end"]) <= 16 and 1 <= len(doc["per_layer"]) <= 128
    # the full check with 24 cells fits the driver's 43200 s
    runs = 2 + 14 * 24
    assert runs * (doc["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_entries_have_just_their_keys_and_well_formed_names(doc):
    for c in doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["reduced"]) <= 16
        assert any(c["file"].startswith(p + "/") for p in doc["paths"])
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    assert len({c["file"] for c in doc["configs"]}) == len(doc["configs"])
    for w in doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert w["config"] in {c["name"] for c in doc["configs"]}
    assert {w["config"] for w in doc["workloads"]} == \
        {c["name"] for c in doc["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in doc["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in doc["workloads"])
    assert four <= max(1, len(doc["workloads"]) // 4)
    cells = {w["name"] for w in doc["workloads"]}
    names = []
    for m in doc["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.1
        names.append(m["name"])
    e2e = set(names)
    assert "setup_s" in e2e
    for m in doc["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
        names.append(m["name"])
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", ())) <= cells
    assert len(set(names)) == len(names)


def test_every_cell_reports_setup_another_metric_and_a_layer(doc):
    spec = spec_mod.Spec(ROOT)
    for w in doc["workloads"]:
        e2e = [m["name"] for m in spec.end_to_end(w["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = spec.per_layer(w["name"])
        assert layer
        for m in layer:
            assert m["moves"] in e2e
            mdoc = spec.load_json("metrics", m["name"])
            assert mdoc["layer"] == m["layer"]
            assert hasattr(spec.module("readers", mdoc["reader"]), "read")
        cfg = spec.config(w["config"])
        assert hasattr(spec.module("drivers", cfg["driver"]), "run")
        assert hasattr(spec.module("reference", cfg["reference"]), "logits")
        arch = spec.module("arch", cfg["arch"])
        assert all(hasattr(arch, n) for n in
                   ("shapes", "tiny", "PUBLISHED", "WIDTH_KEYS",
                    "build_trainer" if cfg["driver"] == "train"
                    else "build_model"))
        assert spec.traffic(w["name"])["check"]["limits"]


def cut_errors(spec, entry):
    """What a configuration's file fails to state of its cut, held against
    the table its own architecture gives for its source."""
    cfg = spec.config(entry["name"])
    arch = spec.module("arch", cfg["arch"])
    errors = []
    if cfg.get("source") != entry["source"]:
        errors.append("source differs between the file and the entry")
    if list(cfg.get("reduced", ())) != entry["reduced"]:
        errors.append("reduced differs between the file and the entry")
    errors += [f"{k} is missing" for k in ("deployment", "assumed")
               if not cfg.get(k)]
    published = arch.PUBLISHED.get(entry["source"])
    if published is None:
        return errors + [f"arch/{cfg['arch']}.py publishes nothing for the source"]
    for k, v in published.items():
        if k in entry["reduced"]:
            if k in arch.WIDTH_KEYS:
                errors.append(f"{k} is a width and may not be reduced")
            if cfg["reduced"].get(k) != {"published": v, "here": cfg.get(k)}:
                errors.append(f"reduced[{k}] is not published {v}, here {cfg.get(k)}")
        elif k not in cfg or cfg[k] != v:
            errors.append(f"{k} is {cfg.get(k, 'left out')}, published {v}, "
                          f"and not in reduced")
    errors += [f"reduced names {k}, which the source does not publish"
               for k in entry["reduced"] if k not in published]
    return errors


@pytest.mark.parametrize("name", [c["name"] for c in spec_mod.Spec(ROOT).doc["configs"]])
def test_configuration_files_state_the_cut(name):
    spec = spec_mod.Spec(ROOT)
    assert cut_errors(spec, spec._entry("configs", name)) == []


def _snapshot(bench):
    """Every file under ``bench`` with its bytes."""
    out = {}
    for d, _, files in os.walk(bench):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[p] = fh.read()
    return out


def test_a_later_pr_adds_files_and_entries_and_edits_none(tmp_path):
    root = _tiny.make_root(tmp_path / "root")
    bench = os.path.join(root, "benchmark")
    before = _snapshot(bench)

    # new files: a configuration, a mix, a metric and its reader
    with open(os.path.join(bench, "configs", "mistral-7b-v0.3-serve-l8.json")) as f:
        cfg = json.load(f)
    cfg["num_hidden_layers"] = 1
    with open(os.path.join(bench, "configs", "other-serve.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic", "decode-closed.json")) as f:
        mix = json.load(f)
    mix["clients"] = 2
    with open(os.path.join(bench, "traffic", "two-callers.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(bench, "metrics", "finished_share.json"), "w") as f:
        json.dump({"layer": "Engine step", "reader": "ratio_of_facts",
                   "args": {"num": "finished", "den": "attempted"}}, f)
    with open(os.path.join(bench, "readers", "ratio_of_facts.py"), "w") as f:
        f.write("def read(facts, num, den):\n"
                "    return 100.0 * facts[num] / facts[den] if facts.get(den) else None\n")
    # new entries
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["configs"].append({"name": "other-serve", "source": "x",
                           "file": "benchmark/configs/other-serve.json",
                           "reduced": ["num_hidden_layers"], "why": "y"})
    doc["workloads"].append({"name": "other.two", "config": "other-serve",
                             "traffic": "two-callers", "chips": 1, "why": "z"})
    for m in doc["end_to_end"]:
        if "workloads" in m and "mistral7b-decode-closed" in m["workloads"]:
            m["workloads"].append("other.two")
    doc["per_layer"].append({"name": "finished_share", "unit": "%",
                             "better": "higher", "source": "program_counter",
                             "layer": "Engine step",
                             "moves": "output_tokens_per_s",
                             "workloads": ["other.two"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)

    spec = spec_mod.Spec(root)
    assert spec.config("other-serve")["num_hidden_layers"] == 1
    assert spec.traffic("other.two")["clients"] == 2
    names = [m["name"] for m in spec.per_layer("other.two")]
    assert "finished_share" in names and "serve_mfu" in names
    assert "loadgen_lag_p95_ms" not in names and "train_mfu" not in names
    assert "ttft_p50_ms.closed" not in names and "ttft_p90_ms.open" not in names
    ctx = harness.RunContext(spec, "other.two", 1, 1.0, True, False, 0.0, False)
    run = {"facts": {"finished": 3, "attempted": 4, "window_s": 1.0,
                     "decode_contexts": [], "prefill_lens": []},
           "trace": None}
    got = harness.read_per_layer(ctx, run, {"kind": "cpu"})
    # the new metric reads; every device metric finds nothing and is left out
    assert got == {"finished_share": {"value": 75.0, "unit": "%"}}
    after = _snapshot(bench)
    assert {p: after[p] for p in before} == before, "a file that was there was edited"


# a later PR's second architecture, as the files it would bring: other
# leaves (fused qkv, a router, stacked experts), a window on every layer,
# top-2 of 8 experts, and the counts that follow from those
OTHER_ARCH = '''
PUBLISHED = {"paper:other-moe": {
    "vocab_size": 4096, "d_model": 512, "d_expert": 256, "n_layers": 12,
    "n_heads": 8, "d_head": 64, "n_experts": 8, "experts_per_token": 2,
    "window": 128}}
WIDTH_KEYS = ("d_model", "d_expert", "d_head", "experts_per_token")


def tiny(cfg):
    return dict(vocab_size=64, d_model=32, d_expert=16, n_layers=2, n_heads=2,
                d_head=16, window=8)


def build_model(cfg, max_positions):
    raise NotImplementedError("the program has no such model yet")


def shapes(cfg):
    d, f, e = cfg["d_model"], cfg["d_expert"], cfg["n_experts"]
    out = {"wte": (cfg["vocab_size"], d)}
    for i in range(cfg["n_layers"]):
        out[f"h.{i}.ln"] = (d,)
        out[f"h.{i}.wqkv"] = (d, 3 * cfg["n_heads"] * cfg["d_head"])
        out[f"h.{i}.wo"] = (cfg["n_heads"] * cfg["d_head"], d)
        out[f"h.{i}.router"] = (d, e)
        out[f"h.{i}.w_in"] = (e, d, f)
        out[f"h.{i}.w_out"] = (e, f, d)
    return out


def _token_matmuls(cfg):
    d, hd = cfg["d_model"], cfg["n_heads"] * cfg["d_head"]
    per_layer = (4 * d * hd + d * cfg["n_experts"]
                 + cfg["experts_per_token"] * 2 * d * cfg["d_expert"])
    return cfg["n_layers"] * per_layer + d * cfg["vocab_size"]


def _seen(cfg, context):
    return min(context, cfg["window"])


def decode_flops(cfg, context):
    attn = 4 * cfg["n_heads"] * cfg["d_head"] * _seen(cfg, context)
    return 2 * _token_matmuls(cfg) + cfg["n_layers"] * attn


def prefill_flops(cfg, n_prompt):
    return sum(decode_flops(cfg, c) for c in range(1, n_prompt + 1))


def paged_attention_decode(cfg, contexts, dtype_bytes=2):
    seen = sum(_seen(cfg, c) for c in contexts)
    width = cfg["n_layers"] * cfg["n_heads"] * cfg["d_head"]
    return {"flops": 4 * width * seen,
            "bytes": 2 * width * dtype_bytes * (seen + len(contexts))}
'''

OTHER_CONFIG = {
    "vocab_size": 4096, "d_model": 512, "d_expert": 256, "n_layers": 4,
    "n_heads": 8, "d_head": 64, "n_experts": 8, "experts_per_token": 2,
    "window": 128, "dtype": "bfloat16", "source": "paper:other-moe",
    "reduced": {"n_layers": {"published": 12, "here": 4}},
    "assumed": ["weights are random from --seed"],
    "deployment": "4 of 12 layers on one chip; no width is cut",
    "driver": "serve", "arch": "other", "reference": "other",
    "engine": {"block_size": 16, "max_slots": 32, "max_model_len": 2048}}


def _write(path, content):
    with open(path, "w") as f:
        if isinstance(content, str):
            f.write(content)
        else:
            json.dump(content, f)


def test_a_later_pr_adds_a_second_architecture_as_files_and_entries(tmp_path):
    from benchmark.lib import peaks, trace as T, weights

    up = _tiny.copy_root(tmp_path / "upstream")   # the benchmark as it stands
    bench = os.path.join(up, "benchmark")
    before = _snapshot(bench)

    # new files: the architecture, its reference, configurations naming it
    _write(os.path.join(bench, "arch", "other.py"), OTHER_ARCH)
    _write(os.path.join(bench, "reference", "other.py"),
           "def logits(cfg, w, tokens, linear=None):\n"
           "    return w['wte'][tokens] @ w['wte'].T\n")
    _write(os.path.join(bench, "configs", "other-serve.json"), OTHER_CONFIG)
    # ... and two that must not pass: a width cut, a cut that is not stated
    _write(os.path.join(bench, "configs", "other-narrow.json"), dict(
        OTHER_CONFIG, d_expert=128, reduced=dict(
            OTHER_CONFIG["reduced"], d_expert={"published": 256, "here": 128})))
    _write(os.path.join(bench, "configs", "other-unstated.json"),
           dict(OTHER_CONFIG, n_experts=4))
    # new entries
    with open(os.path.join(up, "BENCHMARK.json")) as f:
        doc = json.load(f)
    for name, reduced in (("other-serve", ["n_layers"]),
                          ("other-narrow", ["n_layers", "d_expert"]),
                          ("other-unstated", ["n_layers"])):
        doc["configs"].append({"name": name, "source": "paper:other-moe",
                               "file": f"benchmark/configs/{name}.json",
                               "reduced": reduced, "why": "y"})
    doc["workloads"].append({"name": "other.closed", "config": "other-serve",
                             "traffic": "decode-closed", "chips": 1,
                             "why": "z"})
    for m in doc["end_to_end"]:
        if "workloads" in m and "mistral7b-decode-closed" in m["workloads"]:
            m["workloads"].append("other.closed")
    _write(os.path.join(up, "BENCHMARK.json"), doc)

    spec = spec_mod.Spec(up)
    entries = {c["name"]: c for c in spec.doc["configs"]}
    # the cut is held against the architecture's own table
    for name in ("other-serve", "mistral-7b-v0.3-serve-l8",
                 "mistral-7b-v0.3-train-l2"):
        assert cut_errors(spec, entries[name]) == [], name
    assert cut_errors(spec, entries["other-narrow"]) == \
        ["d_expert is a width and may not be reduced"]
    assert cut_errors(spec, entries["other-unstated"]) == \
        ["n_experts is 4, published 8, and not in reduced"]

    # the shares of a peak stay one metric each and read its counts
    ctx = harness.RunContext(spec, "other.closed", 1, 1.0, True, False, 0.0,
                             False)
    cfg, arch = ctx.cfg, ctx.arch
    names = [m["name"] for m in spec.per_layer("other.closed")]
    assert "serve_mfu" in names and "paged_attention_roofline" in names
    assert "train_mfu" not in names
    d = "/device:TPU:0"
    tr = T.Trace.from_json({"modules": {d: [["jit_decode(1)", 0, 1000]]},
                            "ops": {d: [["paged_attention.1", 100, 500]]},
                            "host": [], "window": [0, 1000]})
    run = {"facts": {"window_s": 2.0, "decode_contexts": [100, 300],
                     "prefill_lens": [3]}, "trace": tr}
    ctx.require_chip = True                  # a peak to take a share of
    got = harness.read_per_layer(ctx, run, {"kind": "TPU v5 lite"})
    peak = peaks.peaks("TPU v5 lite")
    # a token passes 2 of 8 experts; the window keeps 128 of 300 positions
    token = 2 * (4 * (4 * 512 * 512 + 512 * 8 + 2 * 2 * 512 * 256)
                 + 512 * 4096)
    attn = 4 * 4 * 8 * 64
    assert arch.decode_flops(cfg, 300) == token + attn * 128
    flops = (3 * token + attn * (1 + 2 + 3)) + (2 * token + attn * (100 + 128))
    assert got["serve_mfu"]["value"] == pytest.approx(
        100 * flops / 2.0 / peak["bf16_flops_per_s"])
    kv_bytes = 2 * (4 * 8 * 64) * 2 * (100 + 128 + 2)
    assert got["paged_attention_roofline"]["value"] == pytest.approx(
        100 * (kv_bytes / peak["hbm_bytes_per_s"]) / 500e-9)
    # ... and not Llama's, which would count the whole context and no expert
    llama = spec.module("arch", "llama")
    mistral = spec.config("mistral-7b-v0.3-serve-l8")
    assert llama.paged_attention_decode(mistral, [100, 300])["bytes"] != kv_bytes
    # a count the architecture does not give is a metric left out
    facts = dict(run["facts"], cfg=cfg, arch=arch, peaks=peak, chips=1,
                 trace=tr, train_tokens=8, traffic={"seq": 4, "batch": 2})
    from benchmark.readers import mfu, roofline
    assert mfu.read(facts, "train") is None
    assert roofline.read(facts, "paged_attention", "flash_attention_train") is None

    # weights come out in its leaves, each from its place in them
    w = weights.make_weights(arch.shapes(cfg), 2**31 + 9, cfg["dtype"])
    assert {n: a.shape for n, a in w.items()} == arch.shapes(cfg)
    assert w["h.3.w_in"].shape == (8, 512, 256) and str(w["wte"].dtype) == "bfloat16"
    assert abs(float(w["h.0.ln"].astype("float32").mean()) - 1.0) < 0.05

    # the CPU rehearsal cuts each configuration by its own architecture
    root = _tiny.make_root(tmp_path / "root", src=up)
    small = spec_mod.Spec(root)
    other = small.config("other-serve")
    assert (other["d_model"], other["n_layers"], other["window"]) == (32, 2, 8)
    assert other["n_experts"] == 8 and "hidden_size" not in other
    mistral = small.config("mistral-7b-v0.3-serve-l8")
    assert mistral["hidden_size"] == 128 and "d_model" not in mistral
    assert mistral["engine"]["max_slots"] == 4

    after = _snapshot(bench)
    assert {p: after[p] for p in before} == before, "a file that was there was edited"


def test_unknown_names_are_errors():
    spec = spec_mod.Spec(ROOT)
    with pytest.raises(spec_mod.SpecError):
        spec.cell("no-such-cell")
    with pytest.raises(spec_mod.SpecError):
        spec.find("traffic", "no-such-mix")


def test_compile_counter_counts_a_new_shape_and_not_a_cached_call():
    import jax
    import jax.numpy as jnp

    counter = harness.CompileCounter().arm()
    f = jax.jit(lambda x: x * 3 + 1)
    f(jnp.ones(5))
    first = counter.n
    assert first > 0
    f(jnp.ones(5))
    assert counter.n == first          # nothing compiles on a warmed shape
    f(jnp.ones(6))
    assert counter.n > first           # a shape that was not warmed does
