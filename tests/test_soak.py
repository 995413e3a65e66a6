"""Rolling-chaos soak harness (paddle_tpu.serving.soak): the tier-1
smoke — a real LocalReplica fleet + journaled gateway replaying a
seeded bursty workload under rotating chaos with every pass criterion
asserted per epoch — plus the journal compaction bounded-soak and the
chaos_run scenario-catalog gate.

The smoke is sized for tier-1 (≲30 s wall on a 1-core CPU host): one
replica, four epochs, degradation plans only (no SIGKILL — killing the
only replica makes accepted-request loss likely by construction, which
is a capacity fact, not a robustness bug). ``chaos_run --suite soak``
runs the full ProcReplica battery.
"""
import os
import sys
import threading
import time

import pytest

from paddle_tpu.serving.journal import Journal, scan_dir
from paddle_tpu.serving.soak import SoakConfig, run_soak
from paddle_tpu.serving.workload import WorkloadSpec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

pytestmark = pytest.mark.soak


def _segments(root):
    return sorted(p for p in os.listdir(root) if p.startswith("wal-"))


class TestSoakSmoke:
    def test_rolling_chaos_smoke(self, tmp_path):
        spec = WorkloadSpec(
            name="smoke", seed=5, requests=24, vocab=64,
            arrival={"kind": "bursty", "calm_qps": 8.0,
                     "burst_qps": 80.0, "mean_calm_s": 0.6,
                     "mean_burst_s": 0.25},
            prompt_len={"kind": "lognormal", "median": 8, "sigma": 0.4,
                        "min": 2, "max": 16},
            output_len={"kind": "lognormal", "median": 6, "sigma": 0.3,
                        "min": 2, "max": 8},
            # liveness SLO: the floor asks "did requests finish", not
            # "was TTFT competitive on a shared-core CI box"
            slo={"ttft_s": 10.0, "tpot_s": 2.0})
        fleet_spec = {
            "seed": 0,
            "llama_tiny": {"vocab": 64, "hidden": 64, "layers": 1,
                           "heads": 4, "kv_heads": 2, "inter": 128,
                           "seq": 48},
            "engine": {"block_size": 4, "max_slots": 3,
                       "max_model_len": 24},
            "warmup": [4, 8, 16],
            "stats_interval_s": 0.05,
        }
        cfg = SoakConfig(
            spec=spec, fleet_spec=fleet_spec, workdir=str(tmp_path),
            epochs=4, replicas=1, fleet="local",
            chaos=[
                # real fault sites (utils.faults catalog) — a typo'd
                # site would arm a plan that never fires
                {"kind": "plan",
                 "plan": "gateway.journal.append:delay=0.005%0.2"},
                {"kind": "compact"},
                {"kind": "plan", "plan": "serving.decode:delay=0.002%0.1"},
                {"kind": "none"},
            ],
            journal={"segment_max_records": 8, "compact_segments": 2,
                     "retain_terminal": 16},
            goodput_floor=0.3, kill_allowed=False)
        report = run_soak(cfg)
        assert report["passed"], report["violations"]
        assert report["violations"] == []
        # zero lost accepted requests, every epoch
        assert all(row["lost"] == 0 for row in report["epochs"])
        # leak sentinel stayed quiet (a leak is an epoch violation, but
        # assert the flags directly too)
        for row in report["epochs"]:
            assert not row.get("leaks"), row
        # journal compaction actually cycled under live traffic
        assert report["compaction_cycles_observed"] >= 1
        # replay is the seeded spec, byte-for-byte attributable
        assert report["fingerprint"]
        assert len(report["epochs"]) == 4


class TestJournalCompactionSoak:
    def test_bounds_hold_across_compaction_cycles_with_live_traffic(
            self, tmp_path):
        root = str(tmp_path)
        j = Journal(root, segment_max_records=6, compact_segments=2,
                    retain_terminal=10)
        stop = threading.Event()

        def writer():
            i = 0
            while not stop.is_set():
                jid = f"r{i}"
                j.accept(jid, gateway_id="gw", prompt=[i % 7],
                         sampling={})
                j.mark(jid, 1, [i % 5])
                j.end(jid, state="finished", tokens=[i % 5])
                i += 1
                time.sleep(0.001)

        th = threading.Thread(target=writer, name="journal-soak-writer",
                              daemon=True)
        th.start()
        seg_cap = 2 + 2           # compact_segments + live + snapshot
        byte_cap = (10 + 6 * seg_cap) * 2048
        oldest_seen = []
        max_segs = max_bytes = 0
        try:
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                j.compact()
                segs = _segments(root)
                if segs:
                    oldest = int(segs[0].split("-")[1].split(".")[0])
                    if not oldest_seen or oldest > oldest_seen[-1]:
                        oldest_seen.append(oldest)
                    max_segs = max(max_segs, len(segs))
                    max_bytes = max(max_bytes, sum(
                        os.path.getsize(os.path.join(root, s))
                        for s in segs))
                if len(oldest_seen) >= 4:     # >= 3 full cycles
                    break
                time.sleep(0.05)
        finally:
            stop.set()
            th.join(5)
            j.close()
        assert len(oldest_seen) >= 4, oldest_seen
        assert max_segs <= seg_cap, (max_segs, seg_cap)
        assert max_bytes <= byte_cap, (max_bytes, byte_cap)
        # the journal stayed scannable mid-soak: terminal retention
        # bounded, no torn state
        s = scan_dir(root)
        assert len(s.terminal()) <= 10 + 6 * seg_cap


class TestScenarioCatalog:
    @pytest.fixture(scope="class")
    def chaos_run(self):
        from tools import chaos_run
        return chaos_run

    def test_unknown_scenario_exits_nonzero_naming_catalog(
            self, chaos_run):
        with pytest.raises(SystemExit) as ei:
            chaos_run.run_sweep(
                ["--suite", "serve-fleet", "--scenario", "bogus"])
        msg = str(ei.value.code)
        # non-zero exit: a string SystemExit code means rc 1
        assert not isinstance(ei.value.code, int) or ei.value.code != 0
        assert "bogus" in msg
        # names its own suite's valid scenarios...
        assert "sigkill" in msg and "drain_restart" in msg
        # ...and the full catalog including the soak suite
        assert "full catalog" in msg
        assert "--suite soak" in msg and "rolling" in msg

    def test_unknown_scenario_rejected_for_every_suite(self, chaos_run):
        for suite in chaos_run.SUITE_SCENARIOS:
            if suite == "perf":      # perf refuses --scenario entirely
                continue
            with pytest.raises(SystemExit):
                chaos_run.run_sweep(
                    ["--suite", suite, "--scenario", "definitely-not"])

    def test_catalog_covers_every_suite_choice(self, chaos_run):
        assert set(chaos_run.SUITE_SCENARIOS) == {
            "serving", "prefix", "spill", "perf", "serve-fleet",
            "durable", "kvfabric", "tenancy", "train", "straggler",
            "locksan", "soak", "alerts", "heal"}
