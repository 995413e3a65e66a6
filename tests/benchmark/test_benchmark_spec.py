"""BENCHMARK.json against the contract, and discovery by name: a later PR
adds a configuration, a mix, a metric and a reader as new files and entries
and edits no file that is there."""
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
        assert spec.traffic(w["name"])["check"]["limits"]


def test_configuration_files_state_the_cut(doc):
    published = {"vocab_size": 32768, "hidden_size": 4096,
                 "intermediate_size": 14336, "num_attention_heads": 32,
                 "num_key_value_heads": 8, "rope_theta": 1e6,
                 "rms_norm_eps": 1e-5, "max_position_embeddings": 32768,
                 "tie_word_embeddings": False, "sliding_window": None}
    for c in doc["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        for k, v in published.items():
            assert cfg[k] == v, (c["name"], k)      # no width is cut
        assert list(cfg["reduced"]) == c["reduced"] == ["num_hidden_layers"]
        assert cfg["reduced"]["num_hidden_layers"] == {
            "published": 32, "here": cfg["num_hidden_layers"]}
        assert cfg["source"] == c["source"] and cfg["deployment"]


def test_a_later_pr_adds_files_and_entries_and_edits_none(tmp_path):
    root = _tiny.make_root(tmp_path / "root")
    bench = os.path.join(root, "benchmark")
    before = {}
    for d, _, files in os.walk(bench):
        for f in files:
            p = os.path.join(d, f)
            before[p] = open(p, "rb").read()

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
    for p, content in before.items():
        assert open(p, "rb").read() == content, f"{p} was edited"


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
