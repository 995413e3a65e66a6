"""Trace-driven workload engine (paddle_tpu.serving.workload) + the
capacity planner's pure math (tools/capacity_plan.py).

The acceptance contract under test: a (spec, seed) pair replays to a
byte-identical schedule — same fingerprint, same request stream — so a
soak or bench regression is reproducible from its JSON artifact alone.
"""
import os
import sys
import threading
import time
from collections import Counter

import pytest

from paddle_tpu.serving.workload import (
    ClosedLoopRunner, OpenLoopRunner, PRESETS, WorkloadError,
    WorkloadSpec, generate, load_spec, preset, summarize)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools import capacity_plan  # noqa: E402

pytestmark = pytest.mark.soak


def _spec(**kw):
    base = dict(
        name="t", seed=7, requests=40, vocab=64,
        arrival={"kind": "poisson", "rate_qps": 20.0},
        prompt_len={"kind": "lognormal", "median": 12, "sigma": 0.5,
                    "min": 2, "max": 48},
        output_len={"kind": "lognormal", "median": 8, "sigma": 0.4,
                    "min": 1, "max": 24})
    base.update(kw)
    return WorkloadSpec(**base)


# ---------------------------------------------------------------------------
# determinism / replay

class TestReplayDeterminism:
    def test_same_spec_same_seed_identical_schedule(self):
        a, b = generate(_spec()), generate(_spec())
        assert a.fingerprint() == b.fingerprint()
        for ra, rb in zip(a, b):
            assert ra == rb          # frozen dataclasses: field equality

    def test_json_round_trip_replays_identically(self):
        spec = _spec()
        clone = WorkloadSpec.from_json(spec.to_json())
        assert generate(clone).fingerprint() == generate(spec).fingerprint()

    def test_seed_changes_schedule(self):
        assert (generate(_spec(seed=1)).fingerprint()
                != generate(_spec(seed=2)).fingerprint())

    def test_spec_knob_changes_schedule(self):
        assert (generate(_spec()).fingerprint()
                != generate(_spec(requests=41)).fingerprint())

    def test_all_presets_generate_deterministically(self):
        for name in PRESETS:
            spec = preset(name)
            assert (generate(spec).fingerprint()
                    == generate(preset(name)).fingerprint()), name

    def test_load_spec_path_and_preset(self, tmp_path):
        p = tmp_path / "wl.json"
        p.write_text(_spec().to_json())
        assert (generate(load_spec(str(p))).fingerprint()
                == generate(_spec()).fingerprint())
        assert load_spec("steady").name == "steady"


# ---------------------------------------------------------------------------
# validation

class TestValidation:
    def test_unknown_arrival_kind(self):
        with pytest.raises(WorkloadError):
            _spec(arrival={"kind": "fractal", "rate_qps": 1}).validate()

    def test_unknown_length_kind(self):
        with pytest.raises(WorkloadError):
            _spec(prompt_len={"kind": "cauchy", "median": 5}).validate()

    def test_bad_mode(self):
        with pytest.raises(WorkloadError):
            _spec(mode="half-open").validate()

    def test_nonpositive_requests(self):
        with pytest.raises(WorkloadError):
            _spec(requests=0).validate()

    def test_tenant_weights_must_be_positive(self):
        with pytest.raises(WorkloadError):
            _spec(tenants=[{"name": "a", "weight": -1}]).validate()


# ---------------------------------------------------------------------------
# distribution properties

class TestDistributions:
    def test_truncation_to_engine_limits(self):
        wl = generate(_spec(
            prompt_len={"kind": "fixed", "value": 1000},
            output_len={"kind": "fixed", "value": 1000}),
            max_model_len=32)
        for r in wl:
            assert len(r.prompt) <= 31
            assert len(r.prompt) + r.max_new_tokens <= 32

    def test_poisson_rate_roughly_matches(self):
        wl = generate(_spec(requests=400,
                            arrival={"kind": "poisson", "rate_qps": 50.0},
                            seed=3))
        assert 35.0 < wl.offered_qps < 70.0

    def test_bursty_has_both_phases(self):
        wl = generate(_spec(requests=200, seed=5, arrival={
            "kind": "bursty", "calm_qps": 4.0, "burst_qps": 200.0,
            "mean_calm_s": 1.0, "mean_burst_s": 0.2}))
        phases = {r.phase for r in wl}
        assert phases == {"calm", "burst"}

    def test_diurnal_phases(self):
        wl = generate(_spec(requests=200, seed=5, arrival={
            "kind": "diurnal", "mean_qps": 20.0, "depth": 0.8,
            "period_s": 4.0}))
        assert {r.phase for r in wl} == {"peak", "trough"}
        assert all(a.at_s <= b.at_s for a, b in zip(wl, list(wl)[1:]))

    def test_tenant_mix_follows_weights(self):
        wl = generate(_spec(requests=300, seed=11, tenants=[
            {"name": "big", "weight": 3.0},
            {"name": "small", "weight": 1.0}]))
        counts = Counter(r.tenant for r in wl)
        assert counts["big"] > counts["small"] * 2

    def test_prefix_share_groups_share_prefixes(self):
        wl = generate(_spec(requests=100, seed=13,
                            prefix={"share": 0.5, "groups": 3}))
        grouped = [r for r in wl if r.group >= 0]
        assert grouped
        by_group = {}
        for r in grouped:
            by_group.setdefault(r.group, []).append(r)
        for members in by_group.values():
            if len(members) < 2:
                continue
            shared = min(int(round(0.5 * len(m.prompt)))
                         for m in members)
            first = members[0].prompt[:shared]
            assert all(m.prompt[:shared] == first for m in members)


# ---------------------------------------------------------------------------
# runners (fake fleet — no engines)

def _instant_ok(wreq):
    return lambda: {"outcome": "ok", "ttft": 0.01,
                    "tokens": wreq.max_new_tokens}


class TestRunners:
    def test_open_loop_counts_sheds_and_lost(self):
        spec = _spec(requests=12,
                     arrival={"kind": "uniform", "rate_qps": 200.0})
        wl = generate(spec)

        def submit(wreq):
            if wreq.index % 3 == 0:
                raise RuntimeError("admission refused")
            if wreq.index % 3 == 1:
                return lambda: {"outcome": "ok", "ttft": 0.01, "tokens": 4}
            return lambda: {"outcome": "lost", "error": "stuck"}

        res = OpenLoopRunner(wl, submit, max_wait_s=10).run()
        s = summarize(res)
        assert s["outcomes"] == {"shed": 4, "ok": 4, "lost": 4}
        assert s["lost"] == 4

    def test_open_loop_arrival_times_respected(self):
        spec = _spec(requests=8,
                     arrival={"kind": "uniform", "rate_qps": 40.0})
        wl = generate(spec)
        seen = []

        def submit(wreq):
            seen.append((wreq.index, time.monotonic()))
            return _instant_ok(wreq)

        t0 = time.monotonic()
        OpenLoopRunner(wl, submit, max_wait_s=10).run()
        for (i, at), r in zip(sorted(seen), wl):
            assert at - t0 >= r.at_s - 0.01

    def test_closed_loop_bounds_concurrency(self):
        spec = _spec(requests=30, mode="closed",
                     closed={"concurrency": 3, "think_time_s": 0.0})
        wl = generate(spec)
        lock = threading.Lock()
        state = {"cur": 0, "peak": 0}

        def submit(wreq):
            with lock:
                state["cur"] += 1
                state["peak"] = max(state["peak"], state["cur"])

            def finish():
                time.sleep(0.005)
                with lock:
                    state["cur"] -= 1
                return {"outcome": "ok", "ttft": 0.001, "tokens": 1}
            return finish

        res = ClosedLoopRunner(wl, submit, max_wait_s=30).run()
        assert len(res) == 30
        assert state["peak"] <= 3

    def test_summarize_goodput_respects_slo(self):
        spec = _spec(requests=10,
                     arrival={"kind": "uniform", "rate_qps": 1000.0})
        wl = generate(spec)

        def submit(wreq):
            ttft = 0.01 if wreq.index < 5 else 9.0
            return lambda: {"outcome": "ok", "ttft": ttft, "tokens": 1}

        res = OpenLoopRunner(wl, submit, max_wait_s=10).run()
        s = summarize(res, slo={"ttft_s": 1.0})
        assert s["goodput_requests"] == 5
        assert s["goodput_ratio"] == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# capacity planner math

class TestCapacityPlanner:
    def test_erlang_c_saturated_queue_always_waits(self):
        assert capacity_plan.erlang_c(2, 2.5) == 1.0
        assert capacity_plan.queue_wait_s(1, 10.0, 5.0) == float("inf")

    def test_queue_wait_shrinks_with_servers(self):
        waits = [capacity_plan.queue_wait_s(c, 8.0, 3.0)
                 for c in (3, 4, 6, 10)]
        assert all(a > b for a, b in zip(waits, waits[1:]))

    def test_peak_concurrency_counts_overlap(self):
        wl = generate(_spec(requests=10,
                            arrival={"kind": "uniform",
                                     "rate_qps": 100.0}))
        # 10 arrivals over 90ms, 1s service: all overlap
        assert capacity_plan.peak_concurrency(wl, 1.0) == 10
        # sub-gap service: never more than one in flight
        assert capacity_plan.peak_concurrency(wl, 0.005) == 1

    def test_throughput_binding(self):
        p = capacity_plan.plan(
            qps=100.0, mean_out=20.0, slo_ttft_s=None, slo_tpot_s=None,
            tok_per_sec=500.0, headroom=1.0)
        assert p["n_throughput"] == 4
        assert p["replicas"] == 4
        assert p["binding_constraint"] == "throughput"

    def test_admission_binding(self):
        p = capacity_plan.plan(
            qps=5.0, mean_out=4.0, slo_ttft_s=None, slo_tpot_s=None,
            tok_per_sec=1000.0, admission_per_replica=10, peak_conc=25)
        assert p["n_admission"] == 3
        assert p["replicas"] == 3
        assert p["binding_constraint"] == "admission"

    def test_latency_binding_adds_servers(self):
        # near-saturated single server: Erlang-C forces more replicas
        # than the pure throughput floor at headroom 1.0
        p = capacity_plan.plan(
            qps=9.0, mean_out=10.0, slo_ttft_s=0.05, slo_tpot_s=None,
            tok_per_sec=100.0, headroom=1.0)
        assert p["n_latency"] > p["n_throughput"]
        assert p["replicas"] == p["n_latency"]

    def test_tpot_slo_derates_throughput(self):
        p = capacity_plan.plan(
            qps=10.0, mean_out=10.0, slo_ttft_s=None, slo_tpot_s=0.01,
            tok_per_sec=1000.0, tpot_s=0.02, headroom=1.0)
        assert p["t_rep_tok_per_sec"] == pytest.approx(500.0)
        assert p["notes"]

    def test_always_at_least_one_replica(self):
        p = capacity_plan.plan(
            qps=0.001, mean_out=1.0, slo_ttft_s=None, slo_tpot_s=None,
            tok_per_sec=1e6)
        assert p["replicas"] == 1
