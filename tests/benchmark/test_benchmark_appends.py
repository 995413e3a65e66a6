"""The contract that a later PR adds its configuration, its cell and its
per-layer metrics at the ends of the lists of BENCHMARK.json, held against
the benchmark's own tests. Each test that reads the lists' order or members
runs on a copy of the benchmark with one more serve configuration, cell and
per-layer metric appended, as such a PR would append them, and passes. Two
copies that break what the tests hold of a block or a cell list fail them,
each for the reason it names."""
import json
import os
import shutil

import pytest

from benchmark.lib import spec as spec_mod

import _tiny
import test_benchmark_falcon_h1 as falcon
import test_benchmark_laguna as laguna
import test_benchmark_program_readers as readers
import test_benchmark_spec as spec_tests
import test_benchmark_token_gap_metrics as token_gaps

NEW_CONFIG, NEW_MIX, NEW_CELL, NEW_METRIC = (
    "appended-serve-l8", "appended-closed", "appended.closed",
    "appended_step_wall_ms")


def _append(root):
    """``root``'s benchmark with one more configuration, traffic mix, cell
    and per-layer metric: copies of files that are there, under new names.
    The cell goes onto every list that names every serve cell."""
    bench = os.path.join(root, "benchmark")
    for kind, old, new in (
            ("configs", "mistral-7b-v0.3-serve-l8", NEW_CONFIG),
            ("traffic", "decode-closed", NEW_MIX),
            ("metrics", "decode_step_wall_ms", NEW_METRIC)):
        shutil.copy(os.path.join(bench, kind, old + ".json"),
                    os.path.join(bench, kind, new + ".json"))
    spec = spec_mod.Spec(root)
    serve = {c["name"] for c in spec.doc["workloads"]
             if spec.config(c["config"])["driver"] == "serve"}

    def edit(doc):
        entry = spec._entry("configs", "mistral-7b-v0.3-serve-l8")
        doc["configs"].append(dict(entry, name=NEW_CONFIG,
                                   file=f"benchmark/configs/{NEW_CONFIG}.json"))
        doc["workloads"].append({
            "name": NEW_CELL, "config": NEW_CONFIG, "traffic": NEW_MIX,
            "chips": 1, "why": "a later PR's serve cell: copies of "
                               "mistral7b-decode-closed's files"})
        for m in doc["end_to_end"] + doc["per_layer"]:
            if set(m.get("workloads", ())) == serve:
                m["workloads"].append(NEW_CELL)
        doc["per_layer"].append({
            "name": NEW_METRIC, "unit": "ms", "better": "lower",
            "source": "program_counter", "layer": "Engine step",
            "moves": "itl_p95_ms", "workloads": [NEW_CELL]})

    _tiny._edit(os.path.join(root, "BENCHMARK.json"), edit)
    return root


@pytest.fixture(scope="module")
def appended(tmp_path_factory):
    return _append(_tiny.copy_root(tmp_path_factory.mktemp("appended")))


@pytest.fixture
def point(monkeypatch):
    """Point the benchmark's tests at another copy of the benchmark."""
    def to(root):
        spec = spec_mod.Spec(root)
        monkeypatch.setattr(_tiny, "ROOT", root)
        monkeypatch.setattr(spec_tests, "ROOT", root)
        for mod in (falcon, laguna, token_gaps):
            monkeypatch.setattr(mod, "SPEC", spec)
        return spec
    return to


def _the_nine(spec):
    for c in spec.doc["workloads"]:
        readers.test_the_nine_are_serve_metrics_added_at_the_end(c["name"])


def _the_four(spec):
    token_gaps.test_the_four_are_one_block_that_every_serve_cell_reports()
    reported = {m["name"] for m in spec.per_layer(NEW_CELL)}
    assert set(token_gaps.FOUR) | {NEW_METRIC} <= reported


def _falcon_cell(spec):
    falcon.test_the_cell_and_its_mix_are_as_the_issue_names_them()


def _laguna_cell(spec):
    laguna.test_the_cell_and_its_mix_are_as_the_issue_names_them()


def _the_contract(spec):
    with open(os.path.join(spec.root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    spec_tests.test_top_level_keys_and_sizes(doc)
    spec_tests.test_entries_have_just_their_keys_and_well_formed_names(doc)
    spec_tests.test_every_cell_reports_setup_another_metric_and_a_layer(doc)
    for c in doc["configs"]:
        spec_tests.test_configuration_files_state_the_cut(c["name"])


@pytest.mark.parametrize("run", [_the_nine, _the_four, _falcon_cell,
                                 _laguna_cell, _the_contract],
                         ids=lambda f: f.__name__.strip("_"))
def test_each_passes_with_one_more_of_each_appended(appended, point, run):
    run(point(appended))


def _move_one_of_falcons_four_to_the_end(doc):
    at = [m["name"] for m in doc["per_layer"]].index("ssm_time_share")
    doc["per_layer"].append(doc["per_layer"].pop(at))


def _drop_the_new_cell_from_one_of_the_nine(doc):
    m = next(m for m in doc["per_layer"] if m["name"] == "decode_step_wall_ms")
    m["workloads"].remove(NEW_CELL)


@pytest.mark.parametrize("edit,run,reason", [
    (_move_one_of_falcons_four_to_the_end, _falcon_cell,
     "do not stand together"),
    (_drop_the_new_cell_from_one_of_the_nine, _the_nine,
     "not every serve cell"),
], ids=["falcons_four_split", "nine_without_the_new_cell"])
def test_a_broken_block_or_cell_list_fails(appended, point, tmp_path, edit,
                                           run, reason):
    root = _tiny.copy_root(tmp_path, src=appended)
    _tiny._edit(os.path.join(root, "BENCHMARK.json"), edit)
    with pytest.raises(AssertionError, match=reason):
        run(point(root))
