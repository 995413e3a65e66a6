"""The four metrics of what a token waited for: each metric file's reader,
``program_counter``, takes its path from a ``stats()`` recorded off a tiny
engine on the CPU (one request admitted while two decode, so every class
the metrics read has gaps), and reads only with a ``peaks`` (a chip). Each
file names a layer that ``BENCHMARK.json`` already names, so that the
entry which reports it in the serve cells is an append alone."""
import json

import numpy as np
import pytest

from benchmark.lib import spec as spec_mod
from benchmark.readers import stat

import _tiny

PEAKS = {"bf16_flops_per_s": 1e12, "int8_ops_per_s": 2e12,
         "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9, "source": "a stand-in"}
FOUR = {"engine_itl_p95_ms": ("perf.token_gaps.all.p95", 1000.0,
                              "Engine step", "itl_p95_ms"),
        "itl_prefill_gap_share": ("perf.token_gaps.prefill.share", 100.0,
                                  "Engine step", "itl_p95_ms"),
        "itl_prefill_gap_ms_p50": ("perf.token_gaps.prefill.p50", 1000.0,
                                   "Engine step", "itl_p95_ms"),
        "first_token_behind_ms_p50": ("perf.first_token.behind.p50", 1000.0,
                                      "Scheduler", "output_tokens_per_s")}
SPEC = spec_mod.Spec(_tiny.ROOT)


@pytest.fixture(scope="module")
def recorded():
    """``stats()`` of a tiny engine as the benchmark's serve cells keep it:
    read after the load, through JSON."""
    import paddle_tpu
    from paddle_tpu.models import LlamaForCausalLM, llama_tiny
    from paddle_tpu.serving import LLMEngine, SamplingParams

    paddle_tpu.seed(0)
    model = LlamaForCausalLM(llama_tiny(vocab=61, hidden=32, layers=2,
                                        heads=4, kv_heads=2, inter=64, seq=64))
    eng = LLMEngine(model, block_size=8, max_slots=3, max_model_len=64)
    rng = np.random.RandomState(0)
    for n in (5, 9):
        eng.add_request(rng.randint(1, 61, n).tolist(),
                        SamplingParams(max_new_tokens=12))
    for _ in range(4):
        eng.step()
    eng.add_request(rng.randint(1, 61, 20).tolist(),
                    SamplingParams(max_new_tokens=6))
    eng.run()
    stats = json.loads(json.dumps(eng.stats()))
    eng.close()
    return stats


@pytest.mark.parametrize("name", sorted(FOUR))
def test_each_reads_its_path_from_a_recorded_stats(recorded, name):
    path, scale, layer, _ = FOUR[name]
    mdoc = SPEC.load_json("metrics", name)
    assert (mdoc["reader"], mdoc["layer"]) == ("program_counter", layer)
    assert mdoc["args"] == {"path": path, "scale": scale}
    reader = SPEC.module("readers", mdoc["reader"])
    want = stat._dig(recorded, path)
    assert want is not None and want > 0
    got = reader.read({"stats": recorded, "peaks": PEAKS}, **mdoc["args"])
    assert got == pytest.approx(scale * want)
    # without a chip, as every host time: nothing
    assert reader.read({"stats": recorded, "peaks": None},
                       **mdoc["args"]) is None


def test_what_they_read_is_consistent(recorded):
    tg = recorded["perf"]["token_gaps"]
    assert sum(tg[c]["share"] for c in ("plain", "prefill", "drain")) \
        == pytest.approx(1.0)
    # two running rows each held the third request's prefill, and the
    # second request's admission behind the first held one more
    assert tg["prefill"]["share"] == pytest.approx(3 / tg["gaps"])
    assert tg["all"]["p50"] <= tg["all"]["p95"] <= tg["all"]["p99"]
    ft = recorded["perf"]["first_token"]
    assert ft["first_tokens"] == 3
    assert "phases" not in recorded["perf"]["decode_step"]


def test_each_names_a_layer_the_benchmark_names():
    layers = {m["layer"] for m in SPEC.doc["per_layer"]}
    for name, (_, _, layer, moves) in FOUR.items():
        assert SPEC.load_json("metrics", name)["layer"] == layer
        assert layer in layers
        assert moves in {m["name"] for m in SPEC.doc["end_to_end"]}


def test_the_four_are_one_block_that_every_serve_cell_reports():
    """Appended to ``per_layer`` together and with no ``workloads`` list:
    each cell that reports the end-to-end metric one moves reports it, so
    every serve cell does, a later one too, and no train cell."""
    for m in _tiny.block(SPEC.doc["per_layer"], list(FOUR)):
        _, _, layer, moves = FOUR[m["name"]]
        assert "workloads" not in m
        assert (m["layer"], m["moves"], m["better"], m["source"]) == (
            layer, moves, "lower", "program_counter")
        assert m["unit"] == ("%" if m["name"].endswith("_share") else "ms")
    for c in SPEC.doc["workloads"]:
        reported = {m["name"] for m in SPEC.per_layer(c["name"])}
        if SPEC.config(c["config"])["driver"] == "serve":
            assert set(FOUR) <= reported, c["name"]
        else:
            assert not set(FOUR) & reported, c["name"]
