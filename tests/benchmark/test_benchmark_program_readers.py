"""The two readers of what the program says about itself: host time under
its phase spans (``span_time_per``) and its own counters
(``program_counter``), against a trace, a ``stats`` and a registry family
made by hand; then the nine metrics that use them, from the facts of a tiny
serve run. Each reads only with a ``peaks`` (a chip): a host time from a CPU
is no more a rate than a device time is."""
import io
import json
import os

import pytest

from benchmark.lib import harness, peaks as peaks_mod, spec as spec_mod
from benchmark.lib import trace as T
from benchmark.readers import program_counter, span_time_per

import _tiny

D = "/device:TPU:0"
PEAKS = {"bf16_flops_per_s": 1e12, "int8_ops_per_s": 2e12,
         "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9, "source": "a stand-in"}
NINE = {"engine_host_ms_per_step", "engine_prepare_ms_per_step",
        "engine_emit_ms_per_step", "replica_loop_ms_per_step",
        "decode_step_wall_ms", "decode_batch_occupancy",
        "engine_queue_wait_ms_p95", "frontdoor_admit_ms_mean",
        "token_relay_ms_mean"}
SERVE = ["mistral7b-decode-closed", "mistral7b-chat-open"]

# two iterations of the replica loop, the second with an admission; ns
HAND = {
    "modules": {D: [["jit_decode(22)", 1900, 3800],
                    ["jit_prefill(11)", 6700, 900],
                    ["jit_decode(22)", 8300, 2200]]},
    "ops": {D: [["fusion.1", 1900, 3800], ["fusion.2", 6700, 900],
                ["fusion.1", 8300, 2200]]},
    "host": [
        ["engine.emit", 900, 150],            # 50 of it inside the window
        ["replica.inbox", 1050, 50], ["engine.schedule", 1100, 100],
        ["engine.assemble", 1200, 300], ["engine.upload", 1500, 200],
        ["engine.decode", 1700, 100], ["engine.overlap", 1800, 500],
        ["engine.decode_wait", 2300, 3500], ["engine.emit", 5800, 200], ["engine.account", 6000, 100],
        ["replica.sweep", 6100, 200],
        ["replica.inbox", 6300, 100], ["engine.schedule", 6400, 100],
        ["engine.prefill", 6500, 300], ["engine.prefill_wait", 6800, 900],
        ["engine.emit", 7700, 50], ["engine.account", 7750, 50],
        ["engine.schedule", 7800, 50], ["engine.assemble", 7850, 150],
        ["engine.upload", 8000, 200], ["engine.decode", 8200, 100],
        ["engine.decode_wait", 8300, 2300],
        ["engine.emit", 10600, 600],          # 400 of it inside the window
        ["replica.idle", 3000, 10], ["bench.other", 2000, 5000]],
    "window": [1000, 11000],
}
GROUPS = {
    "engine_prepare_ms_per_step":
        (100 + 300 + 200 + 100) + (100 + 300 + 50 + 150 + 200 + 100),
    "engine_emit_ms_per_step": 50 + 200 + 50 + 400,
    "replica_loop_ms_per_step": (50 + 100 + 200) + (100 + 50),
}


def _args(name):
    spec = spec_mod.Spec(_tiny.ROOT)
    mdoc = spec.load_json("metrics", name)
    reader = spec.module("readers", mdoc["reader"])
    return reader, mdoc["args"]


def test_span_time_per_against_a_trace_made_by_hand():
    facts = {"trace": T.Trace.from_json(HAND), "peaks": PEAKS}
    per_step = {}
    for name, ns in GROUPS.items():
        reader, args = _args(name)
        per_step[name] = reader.read(facts, **args)
        # two engine.decode spans start in the window
        assert per_step[name] == pytest.approx(ns / 1e6 / 2)
    reader, args = _args("engine_host_ms_per_step")
    host = reader.read(facts, **args)
    # every phase on the way to the next dispatch: the three groups
    assert host == pytest.approx(sum(per_step.values()))
    assert host == pytest.approx(sum(GROUPS.values()) / 1e6 / 2)
    # the waits, what is booked under the device's time, the idle block and
    # a stranger's span are never counted
    assert span_time_per.read(facts, r"^engine\.(decode_wait|overlap)$",
                              "engine.decode") == pytest.approx(
        (500 + 3500 + 2300) / 1e6 / 2)
    assert span_time_per.read(facts, r"^(replica|engine)\.",
                              "engine.decode", exclude=r".") is None
    assert span_time_per.read(facts, r"^engine\.nothing$",
                              "engine.decode") is None
    assert span_time_per.read(facts, r"^engine\.", "engine.step") is None


def test_program_counter_against_stats_and_a_family_made_by_hand():
    from paddle_tpu import telemetry

    stats = {"perf": {"decode_step": {"step_s": {"p50": 0.0781},
                                      "occupancy": {"mean": 0.625}}},
             "slo": {"queue_time": {"p95": 0.0123, "p50": None}}}
    facts = {"stats": stats, "peaks": PEAKS}
    for name, want in (("decode_step_wall_ms", 78.1),
                       ("decode_batch_occupancy", 62.5),
                       ("engine_queue_wait_ms_p95", 12.3)):
        reader, args = _args(name)
        assert reader.read(facts, **args) == pytest.approx(want)
    assert program_counter.read(facts, path="slo.queue_time.p50") is None
    assert program_counter.read(facts, path="perf.no.such") is None
    h = telemetry.registry().histogram(
        "bench_test_relay_seconds", "made by hand", ("engine",))
    assert program_counter.read(
        facts, family="bench_test_relay_seconds") is None     # no sample
    h.labels(engine="a").observe(0.001)
    h.labels(engine="a").observe(0.003)
    h.labels(engine="b").observe(0.008)
    assert program_counter.read(
        facts, family="bench_test_relay_seconds",
        scale=1000.0) == pytest.approx(4.0)
    assert program_counter.read(facts, family="no_such_family") is None
    with pytest.raises(ValueError):
        program_counter.read(facts)
    with pytest.raises(ValueError):
        program_counter.read(facts, path="a", family="b")


@pytest.mark.parametrize("name", sorted(NINE))
def test_without_a_chip_nothing_is_read(name):
    from paddle_tpu import telemetry

    telemetry.registry().histogram(
        "serving_admit_delay_seconds", "", ("engine",)).labels(
        engine="t").observe(0.01)
    stats = {"perf": {"decode_step": {"step_s": {"p50": 0.07},
                                      "occupancy": {"mean": 0.5}}},
             "slo": {"queue_time": {"p95": 0.01}}}
    facts = {"trace": T.Trace.from_json(HAND), "stats": stats}
    reader, args = _args(name)
    assert reader.read(dict(facts, peaks=None), **args) is None
    if name != "token_relay_ms_mean":     # its family: the gateway's alone
        assert reader.read(dict(facts, peaks=PEAKS), **args) > 0


def test_the_nine_are_serve_metrics_added_at_the_end():
    with open(os.path.join(_tiny.ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    added = doc["per_layer"][-9:]
    assert {m["name"] for m in added} == NINE
    spec = spec_mod.Spec(_tiny.ROOT)
    for m in added:
        assert m["workloads"] == SERVE
        mdoc = spec.load_json("metrics", m["name"])
        assert mdoc["layer"] == m["layer"]
        assert m["source"] == {"span_time_per": "program_span",
                               "program_counter": "program_counter"}[
            mdoc["reader"]]
    assert not NINE & {m["name"] for m in spec.per_layer("mistral7b-train-2k")}
    for cell in SERVE:
        assert NINE <= {m["name"] for m in spec.per_layer(cell)}


@pytest.fixture
def pallas_interpret(uninstall_mesh):
    from paddle_tpu import kernels

    kernels.set_use_pallas(True)
    yield
    kernels.set_use_pallas(None)


def test_a_tiny_serve_run_yields_all_nine_with_a_stand_in_peaks(
        tmp_path, pallas_interpret, monkeypatch):
    """The facts of a traced run of the tiny open-loop cell on the CPU,
    read as a chip's would be: the harness is told it has one, with a
    stand-in peaks table. (Without it the same run prints none of the
    nine: test_benchmark_serve_driver.py.)"""
    root = _tiny.make_root(tmp_path / "root")
    real = harness.read_per_layer

    def as_on_a_chip(ctx, run, device):
        ctx.require_chip = True
        return real(ctx, run, device)

    monkeypatch.setattr(harness, "read_per_layer", as_on_a_chip)
    monkeypatch.setattr(peaks_mod, "peaks", lambda kind: PEAKS)
    out = io.StringIO()
    rc, res = harness.run_cell("mistral7b-chat-open", 2**31 + 91, 3.0, True,
                               root=root, require_chip=False, out=out)
    assert rc == 0 and res["correct"] is True
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert NINE <= set(got)
    assert all(got[k] > 0 for k in NINE)
    assert got["engine_host_ms_per_step"] == pytest.approx(
        got["engine_prepare_ms_per_step"] + got["engine_emit_ms_per_step"]
        + got["replica_loop_ms_per_step"])
    assert got["decode_batch_occupancy"] <= 100.0
    assert {m: res["metrics"][m]["unit"] for m in NINE} == {
        m: "%" if m == "decode_batch_occupancy" else "ms" for m in NINE}
