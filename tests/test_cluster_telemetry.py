"""Cluster observability plane + serving SLO tracker (ISSUE 6).

Three layers of evidence:

- pure-logic tests against an in-memory store fake: clock-offset
  estimation under injected skew, straggler/desync/hang diagnosis from
  fabricated heartbeats, clock-corrected trace merging, SLO percentile /
  goodput / shed semantics, prefix fault sites;
- engine integration: ``LLMEngine.stats()["slo"]`` as the gateway-facing
  admit/shed signal;
- spawned multi-process tests over a REAL TCPStore (native runtime
  gated): two ranks with artificial clock skew publish, aggregate, and
  merge traces; an injected collective hang yields a postmortem bundle
  with one entry per rank.
"""
import json
import os
import subprocess
import sys
import time

import pytest

import paddle_tpu
from paddle_tpu import telemetry
from paddle_tpu.telemetry import cluster
from paddle_tpu.telemetry.cluster import (
    ClockResponder, ClusterAggregator, ClusterMonitor, RankPublisher,
    estimate_clock_offset, merge_traces, stack_snapshot)
from paddle_tpu.telemetry.slo import SLOTracker
from paddle_tpu.utils import faults

pytestmark = pytest.mark.telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _DictStore:
    """In-memory stand-in for TCPStore (set/get/add/wait), enough for the
    whole cluster plane, which is duck-typed on exactly these verbs."""

    def __init__(self):
        self.d = {}

    def set(self, key, value):
        self.d[key] = value if isinstance(value, bytes) else \
            str(value).encode()

    def get(self, key):
        return self.d.get(key)

    def add(self, key, amount=1):
        v = int(self.d.get(key, b"0")) + int(amount)
        self.d[key] = str(v).encode()
        return v

    def wait(self, key, timeout=None):
        return key in self.d


# ---------------------------------------------------------------------------
# prefix fault sites (satellite: collective:delay / store verb delay)
# ---------------------------------------------------------------------------

class TestPrefixFaultSites:
    def test_site_matches_semantics(self):
        assert faults.site_matches("collective", "collective.all_reduce")
        assert faults.site_matches("store", "store.get")
        assert faults.site_matches("collective.step", "collective.step")
        assert not faults.site_matches("coll", "collective.step")
        assert not faults.site_matches("collective.all", "collective.all_reduce")
        # dotted spec sites stay exact: no subtree surprise for old plans
        assert not faults.site_matches("serving.decode",
                                       "serving.decode.slot")

    def test_prefix_delay_fires_on_descendant_site(self):
        with faults.FaultPlan.parse("collective:delay=0.01x*") as plan:
            t0 = time.monotonic()
            faults.inject("collective.all_reduce")
            faults.inject("collective.step")
            elapsed = time.monotonic() - t0
        assert plan.fired_at("collective.all_reduce") == 1
        assert plan.fired_at("collective.step") == 1
        assert elapsed >= 0.02

    def test_store_prefix_error(self):
        with faults.FaultPlan.parse("store:error@1"):
            with pytest.raises(faults.FaultError):
                faults.inject("store.get", key="k")

    def test_exact_sites_unchanged(self):
        with faults.FaultPlan.parse("serving.decode:error@1") as plan:
            with pytest.raises(faults.FaultError):
                faults.inject("serving.decode")
            faults.inject("serving.decode.slot")  # sibling: no fire
        assert plan.fired_at("serving.decode.slot") == 0


# ---------------------------------------------------------------------------
# clock sync
# ---------------------------------------------------------------------------

class TestClockSync:
    def test_offset_recovers_injected_skew(self):
        store = _DictStore()
        resp = ClockResponder(store, world_size=1, poll_s=0.001).start()
        try:
            skew = 4.5
            est = estimate_clock_offset(
                store, rank=0, probes=4, timeout_s=5.0,
                clock=lambda: time.time() + skew)
            # offset converts the skewed clock back to responder time
            assert abs(est.offset_s + skew) < 0.25
            assert est.rtt_s < 1.0 and est.probes == 4
        finally:
            resp.stop()

    def test_no_responder_times_out(self):
        with pytest.raises(TimeoutError, match="clock sync"):
            estimate_clock_offset(_DictStore(), rank=0, probes=1,
                                  timeout_s=0.05, poll_s=0.01)


# ---------------------------------------------------------------------------
# straggler / desync / hang diagnosis
# ---------------------------------------------------------------------------

def _publish_coll(store, rank, seq, t_enter, state="entered", op="ar",
                  t_exit=None):
    store.set(f"telemetry/{rank}/coll", json.dumps(
        {"rank": rank, "seq": seq, "op": op, "state": state,
         "t_enter": t_enter, "t_exit": t_exit}))


class TestClusterMonitor:
    def test_persistent_straggler_named_with_seqs(self):
        store = _DictStore()
        mon = ClusterMonitor(store, 3, straggler_threshold_s=0.1,
                             straggler_min_seqs=3)
        t0 = time.time()
        for seq in range(1, 5):
            base = t0 + seq
            for r in range(3):
                late = 0.3 if r == 2 else 0.0
                _publish_coll(store, r, seq, base + late, state="exited",
                              t_exit=base + late + 0.01)
            report = mon.poll()
        named = report["straggler"]
        assert named is not None and named["rank"] == 2
        assert named["seqs"] == [1, 2, 3, 4]
        assert 0.25 < named["mean_lag_s"] < 0.35
        assert named["ops"][1] == "ar"

    def test_clock_offset_correction_prevents_false_straggler(self):
        store = _DictStore()
        mon = ClusterMonitor(store, 2, straggler_threshold_s=0.1,
                             straggler_min_seqs=2)
        t0 = time.time()
        # rank 1's clock runs 5s ahead but it publishes its offset
        store.set("telemetry/1/meta", json.dumps(
            {"rank": 1, "wall": t0 + 5.0, "clock_offset_s": -5.0}))
        store.set("telemetry/0/meta", json.dumps(
            {"rank": 0, "wall": t0, "clock_offset_s": 0.0}))
        for seq in range(1, 5):
            base = t0 + seq
            _publish_coll(store, 0, seq, base)
            _publish_coll(store, 1, seq, base + 5.0)   # skewed stamp
            report = mon.poll()
        assert report["straggler"] is None

    def test_desync_and_behind_ranks(self):
        store = _DictStore()
        mon = ClusterMonitor(store, 3, desync_threshold=2)
        t = time.time()
        _publish_coll(store, 0, 7, t)
        _publish_coll(store, 1, 7, t)
        _publish_coll(store, 2, 4, t)
        report = mon.poll()
        assert report["seq_spread"] == 3
        assert report["desync"] is True
        assert report["behind_ranks"] == [2]

    def test_hang_suspects_the_rank_that_never_arrived(self):
        store = _DictStore()
        mon = ClusterMonitor(store, 3, hang_threshold_s=1.0)
        now = time.time()
        # ranks 0,1 entered seq 6 ten seconds ago and sit there; rank 2
        # exited seq 5 and never entered 6 -> it is the suspect
        _publish_coll(store, 0, 6, now - 10.0)
        _publish_coll(store, 1, 6, now - 10.0)
        _publish_coll(store, 2, 5, now - 12.0, state="exited",
                      t_exit=now - 11.0)
        report = mon.poll()
        assert report["hang"]["hung"] is True
        assert report["hang"]["suspect_ranks"] == [2]
        assert report["hang"]["waiting_ranks"] == [0, 1]
        assert report["hang"]["stuck_for_s"] > 5.0

    def test_quiet_cluster_reports_no_findings(self):
        store = _DictStore()
        mon = ClusterMonitor(store, 2)
        t = time.time()
        _publish_coll(store, 0, 3, t, state="exited", t_exit=t)
        _publish_coll(store, 1, 3, t, state="exited", t_exit=t)
        report = mon.poll()
        assert not report["desync"] and not report["hang"]["hung"]
        assert report["straggler"] is None


# ---------------------------------------------------------------------------
# aggregation + postmortem (in-process, fake store)
# ---------------------------------------------------------------------------

class TestAggregation:
    def test_publish_and_merge_with_rank_labels_and_rollup(self):
        store = _DictStore()
        pubs = [RankPublisher(store, r, 2, sync_clock=False)
                for r in range(2)]
        telemetry.registry().counter(
            "cluster_publish_total").inc(0)  # ensure family exists
        for p in pubs:
            p.publish_once()
        agg = ClusterAggregator(store, 2)
        view = agg.fleet_view()
        assert view["ranks"][0]["meta"]["rank"] == 0
        assert view["ranks"][1]["metrics"] is not None
        merged = agg.merged_snapshot()
        fam = merged["cluster_publish_total"]
        assert "rank" in fam["labels"]
        ranks_seen = {s["labels"]["rank"] for s in fam["series"]}
        assert ranks_seen == {"0", "1"}
        # the rollup is the sum over the per-rank series
        assert fam["rollup"]["value"] == pytest.approx(
            sum(s["value"] for s in fam["series"]))
        text = agg.prometheus_text()
        assert 'cluster_publish_total{rank="0"}' in text

    def test_postmortem_bundle_one_entry_per_rank(self, tmp_path):
        store = _DictStore()
        pubs = [RankPublisher(store, r, 3, sync_clock=False)
                for r in range(3)]
        agg = ClusterAggregator(store, 3)
        # rank 1's collective times out -> it broadcasts the request
        pm_id = pubs[1].trigger_postmortem("collective timeout: all_reduce")
        for p in pubs:
            p.publish_once()          # the other ranks' ticks answer it
        bundle = agg.collect_postmortem(
            "collective timeout: all_reduce", out_dir=str(tmp_path),
            timeout_s=2.0, pm_id=pm_id)
        assert bundle is not None
        manifest = json.load(open(os.path.join(bundle, "manifest.json")))
        assert manifest["ranks_collected"] == [0, 1, 2]
        assert manifest["missing"] == []
        for r in range(3):
            flightdoc = json.load(
                open(os.path.join(bundle, f"rank{r}-flight.json")))
            assert flightdoc["rank"] == r and "flight" in flightdoc
            stacks = open(
                os.path.join(bundle, f"rank{r}-stacks.txt")).read()
            assert "MainThread" in stacks

    def test_missing_rank_listed_not_fatal(self, tmp_path):
        store = _DictStore()
        RankPublisher(store, 0, 2, sync_clock=False).publish_once()
        agg = ClusterAggregator(store, 2)
        pm_id = "pm-test"
        store.set(cluster.PM_REQUEST_KEY,
                  json.dumps({"id": pm_id, "reason": "r"}))
        # only rank 0 answers
        p0 = RankPublisher(store, 0, 2, sync_clock=False)
        p0.answer_postmortem(pm_id, "r")
        bundle = agg.collect_postmortem("r", out_dir=str(tmp_path),
                                        timeout_s=0.2, pm_id=pm_id)
        manifest = json.load(open(os.path.join(bundle, "manifest.json")))
        assert manifest["ranks_collected"] == [0]
        assert manifest["missing"] == [1]

    def test_stack_snapshot_sees_all_threads(self):
        snap = stack_snapshot()
        assert any("MainThread" in k for k in snap)
        main = next(v for k, v in snap.items() if "MainThread" in k)
        assert any("stack_snapshot" in ln or "test_stack" in ln
                   for ln in main)


# ---------------------------------------------------------------------------
# trace merge
# ---------------------------------------------------------------------------

def _trace(epoch_unix, events_us):
    return {"traceEvents": [
        {"ph": "X", "name": n, "pid": 1, "tid": 1, "ts": ts, "dur": 10.0,
         "args": {}} for n, ts in events_us],
        "otherData": {"epoch_unix": epoch_unix}}


class TestMergeTraces:
    def test_skewed_ranks_land_in_true_order(self, tmp_path):
        # rank 0: trace epoch at wall 1000.0, events at +1s and +3s
        # rank 1: process started 2s later; its clock also reads 1.0s
        #   AHEAD, so its raw epoch says 1003.0 while true wall is 1002.0
        t_a = _trace(1000.0, [("a0", 1_000_000.0), ("a1", 3_000_000.0)])
        t_b = _trace(1003.0, [("b0", 500_000.0)])
        out = str(tmp_path / "merged.json")
        merged = merge_traces({0: t_a, 1: t_b}, out_path=out,
                              offsets_s={1: -1.0})
        xs = [e for e in merged["traceEvents"] if e["ph"] == "X"]
        assert [e["ts"] for e in xs] == sorted(e["ts"] for e in xs)
        by_name = {e["name"]: e for e in xs}
        # true wall times: a0=1001.0 a1=1003.0 b0=1002.5; t_zero=1000.0
        assert by_name["a0"]["ts"] == pytest.approx(1_000_000.0)
        assert by_name["b0"]["ts"] == pytest.approx(2_500_000.0)
        assert by_name["a1"]["ts"] == pytest.approx(3_000_000.0)
        assert ["a0", "b0", "a1"] == [e["name"] for e in xs]
        assert by_name["b0"]["pid"] == 1 and by_name["a0"]["pid"] == 0
        assert json.load(open(out))["otherData"]["merged"] is True

    def test_one_process_row_per_rank(self):
        merged = merge_traces({0: _trace(10.0, [("x", 0.0)]),
                               1: _trace(10.0, [("y", 0.0)]),
                               2: _trace(10.0, [("z", 0.0)])})
        names = {e["pid"]: e["args"]["name"]
                 for e in merged["traceEvents"]
                 if e.get("ph") == "M" and e["name"] == "process_name"}
        assert names == {0: "rank 0", 1: "rank 1", 2: "rank 2"}

    def test_bases_override_trumps_trace_epoch(self):
        t = _trace(999.0, [("e", 0.0)])
        merged = merge_traces({0: t, 1: _trace(1000.0, [("f", 0.0)])},
                              bases_unix={0: 1005.0})
        by = {e["name"]: e["ts"] for e in merged["traceEvents"]
              if e.get("ph") == "X"}
        assert by["f"] == pytest.approx(0.0)
        assert by["e"] == pytest.approx(5_000_000.0)


# ---------------------------------------------------------------------------
# SLO tracker
# ---------------------------------------------------------------------------

class TestSLOTracker:
    def test_percentiles_and_goodput(self):
        t = SLOTracker(ttft_slo_s=0.1, tpot_slo_s=0.02, min_samples=1,
                       engine_label="slo-t1")
        for i in range(9):
            t.record_finished(ttft=0.01 * (i + 1), tpot=0.01,
                              queue_time=0.001, tokens=10)
        t.record_finished(ttft=0.5, tpot=0.01, queue_time=0.001, tokens=10)
        s = t.summary()
        assert s["window_requests"] == 10
        assert s["ttft"]["p50"] == pytest.approx(0.05)
        assert s["ttft"]["p99"] == pytest.approx(0.5)
        # 9 within SLO (<=0.1), 1 blown -> 90/100 tokens good
        assert s["goodput_ratio"] == pytest.approx(0.9)
        assert s["request_goodput_ratio"] == pytest.approx(0.9)
        assert s["shed"] is True      # p99 0.5 > 0.1 SLO

    def test_failed_requests_count_against_goodput(self):
        t = SLOTracker(min_samples=1, engine_label="slo-t2")
        t.record_finished(ttft=0.01, tpot=0.01, queue_time=0.0, tokens=8)
        t.record_failed(tokens=8)
        s = t.summary()
        assert s["goodput_ratio"] == pytest.approx(0.5)
        assert s["request_goodput_ratio"] == pytest.approx(0.5)
        assert s["healthy"] is True   # no SLO set: failures waste tokens
        #                               but don't flip the shed signal

    def test_window_pruning(self):
        now = [100.0]
        t = SLOTracker(window_s=10.0, min_samples=1, clock=lambda: now[0],
                       engine_label="slo-t3")
        t.record_finished(ttft=0.01, tpot=None, queue_time=None, tokens=5)
        now[0] = 105.0
        t.record_finished(ttft=0.02, tpot=None, queue_time=None, tokens=5)
        assert t.summary()["window_requests"] == 2
        now[0] = 112.0                # first sample now older than 10s
        s = t.summary()
        assert s["window_requests"] == 1
        assert s["ttft"]["p99"] == pytest.approx(0.02)

    def test_min_samples_guards_shed(self):
        t = SLOTracker(ttft_slo_s=0.001, min_samples=5,
                       engine_label="slo-t4")
        for _ in range(4):
            t.record_finished(ttft=1.0, tpot=None, queue_time=None,
                              tokens=1)
        assert t.summary()["healthy"] is True     # too few to judge
        t.record_finished(ttft=1.0, tpot=None, queue_time=None, tokens=1)
        assert t.summary()["healthy"] is False

    def test_gauges_exported(self):
        t = SLOTracker(ttft_slo_s=0.1, min_samples=1,
                       engine_label="slo-t5")
        t.record_finished(ttft=0.05, tpot=0.01, queue_time=0.0, tokens=3)
        t.summary()
        g = telemetry.registry().get("slo_goodput_ratio")
        assert g.labels(engine="slo-t5").value == pytest.approx(1.0)
        assert telemetry.registry().get("slo_healthy").labels(
            engine="slo-t5").value == 1.0

    def test_disabled_telemetry_records_nothing(self):
        t = SLOTracker(min_samples=1, engine_label="slo-t6")
        telemetry.disable()
        try:
            t.record_finished(ttft=0.5, tpot=0.5, queue_time=0.5, tokens=9)
        finally:
            telemetry.enable()
        assert t.summary()["window_requests"] == 0


# ---------------------------------------------------------------------------
# engine integration: stats()["slo"] is the gateway's admit/shed signal
# ---------------------------------------------------------------------------

def _tiny_model():
    from paddle_tpu.models import LlamaForCausalLM, llama_tiny

    paddle_tpu.seed(0)
    cfg = llama_tiny(vocab=61, hidden=32, layers=2, heads=4, kv_heads=2,
                     inter=64, seq=64)
    return LlamaForCausalLM(cfg)


class TestEngineSLO:
    def test_stats_slo_block_and_goodput(self):
        from paddle_tpu.serving import LLMEngine, SamplingParams

        eng = LLMEngine(_tiny_model(), block_size=8, max_slots=2,
                        max_model_len=32)
        sp = SamplingParams(max_new_tokens=4, temperature=0.0)
        eng.generate([[1, 2, 3], [4, 5, 6], [7, 8]], sp)
        slo = eng.stats()["slo"]
        assert slo["window_requests"] == 3
        assert slo["total_tokens"] == 12
        assert slo["goodput_ratio"] == pytest.approx(1.0)
        assert slo["healthy"] is True and slo["shed"] is False
        assert slo["ttft"]["p99"] is not None

    def test_blown_slo_flips_shed_signal(self):
        from paddle_tpu.serving import LLMEngine, SamplingParams

        eng = LLMEngine(_tiny_model(), block_size=8, max_slots=2,
                        max_model_len=32, slo_ttft_s=1e-9, slo_tpot_s=1e-9)
        eng.slo.min_samples = 2
        sp = SamplingParams(max_new_tokens=3, temperature=0.0)
        eng.generate([[1, 2, 3], [4, 5, 6]], sp)
        slo = eng.stats()["slo"]
        assert slo["goodput_ratio"] == 0.0
        assert slo["shed"] is True and slo["healthy"] is False


# ---------------------------------------------------------------------------
# multi-process: real TCPStore, spawned ranks (the ISSUE acceptance pair)
# ---------------------------------------------------------------------------

def _native_available():
    from paddle_tpu.core import native
    return native.load() is not None


needs_native = pytest.mark.skipif(not _native_available(),
                                  reason="native runtime (csrc/) not built")


def _spawn_rank(endpoint, rank, world, steps, scenario, tmp_path,
                skew=0.0, plan=None):
    trace = str(tmp_path / f"trace-rank{rank}.json")
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               PADDLE_TELEMETRY_STORE=endpoint, DEMO_RANK=str(rank),
               DEMO_WORLD=str(world), DEMO_STEPS=str(steps),
               DEMO_SCENARIO=scenario, DEMO_TRACE_OUT=trace,
               DEMO_LINGER_S="0.2")
    if skew:
        env["DEMO_CLOCK_SKEW"] = str(skew)
    if plan:
        env["FLAGS_fault_plan"] = plan
    proc = subprocess.Popen(
        [sys.executable, "-c",
         "from paddle_tpu.telemetry.cluster import demo_worker; "
         "demo_worker()"],
        env=env, cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    return proc, trace


@needs_native
class TestMultiProcess:
    def test_two_ranks_publish_clock_skew_and_trace_merge(self, tmp_path):
        from paddle_tpu.distributed.tcp_store import TCPStore

        store = TCPStore(is_master=True)
        agg = ClusterAggregator(store, 2)
        agg.start_clock_responder()
        procs = []
        try:
            endpoint = f"127.0.0.1:{store.port}"
            skew = 4.0
            p0, tr0 = _spawn_rank(endpoint, 0, 2, 3, "t2r", tmp_path)
            p1, tr1 = _spawn_rank(endpoint, 1, 2, 3, "t2r", tmp_path,
                                  skew=skew)
            procs = [p0, p1]
            for p in procs:
                assert p.wait(timeout=120) == 0, p.stdout.read()
            view = agg.fleet_view()
            meta1 = view["ranks"][1]["meta"]
            # the store exchange recovered the injected host-clock skew
            assert abs(meta1["clock_offset_s"] + skew) < 0.5
            # both ranks' metrics snapshots landed and merge per-rank
            merged = agg.merged_snapshot()
            fam = merged["cluster_publish_total"]
            assert {s["labels"]["rank"] for s in fam["series"]} == \
                {"0", "1"}
            # heartbeats reached seq = steps on both ranks
            assert view["ranks"][0]["coll"]["seq"] == 3
            assert view["ranks"][1]["coll"]["seq"] == 3
            # merged trace: one process row per rank, offset-corrected
            # monotonic timeline
            bases = {r: view["ranks"][r]["meta"]["trace_epoch_unix"]
                     for r in (0, 1)}
            offs = {r: view["ranks"][r]["meta"]["clock_offset_s"] or 0.0
                    for r in (0, 1)}
            out = str(tmp_path / "merged.json")
            merged_tr = merge_traces({0: tr0, 1: tr1}, out_path=out,
                                     offsets_s=offs, bases_unix=bases)
            xs = [e for e in merged_tr["traceEvents"]
                  if e.get("ph") == "X"]
            assert {e["pid"] for e in xs} == {0, 1}
            assert [e["ts"] for e in xs] == sorted(e["ts"] for e in xs)
            assert all(e["ts"] >= 0 for e in xs)
            # steps synchronize on a barrier: with the ~4s skew corrected,
            # the two ranks' same-step spans must overlap (they'd be
            # seconds apart uncorrected)
            steps0 = {e["args"]["step"]: e for e in xs
                      if e["pid"] == 0 and e["name"] == "demo.step"}
            steps1 = {e["args"]["step"]: e for e in xs
                      if e["pid"] == 1 and e["name"] == "demo.step"}
            for i in steps0:
                a, b = steps0[i], steps1[i]
                assert abs(a["ts"] - b["ts"]) < 1e6   # < 1s apart
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
            agg.stop()
            store.close()

    def test_hang_postmortem_bundle_has_every_rank(self, tmp_path):
        from paddle_tpu.distributed.tcp_store import TCPStore

        store = TCPStore(is_master=True)
        agg = ClusterAggregator(store, 2)
        agg.start_clock_responder()
        mon = ClusterMonitor(store, 2, hang_threshold_s=0.5)
        procs = []
        try:
            endpoint = f"127.0.0.1:{store.port}"
            p0, _ = _spawn_rank(endpoint, 0, 2, 5, "hang", tmp_path)
            # rank 1 wedges before entering its 3rd collective
            p1, _ = _spawn_rank(endpoint, 1, 2, 5, "hang", tmp_path,
                                plan="collective:delay=120@3")
            procs = [p0, p1]
            report = None
            deadline = time.time() + 60
            while time.time() < deadline:
                report = mon.poll()
                # the planted hang is the one at the 3rd collective. While
                # the two processes start, whichever imports faster waits
                # in the 1st for longer than the threshold too, and the
                # monitor rightly says so: that is not the hang under test
                if report["hang"]["hung"] and \
                        report["hang"]["waiting_seq"] == 3:
                    break
                time.sleep(0.05)
            assert report is not None and report["hang"]["hung"]
            assert report["hang"]["waiting_seq"] == 3
            assert report["hang"]["suspect_ranks"] == [1]
            assert report["hang"]["waiting_ranks"] == [0]
            bundle = agg.collect_postmortem(
                "test hang", out_dir=str(tmp_path), timeout_s=15.0)
            assert bundle is not None
            manifest = json.load(
                open(os.path.join(bundle, "manifest.json")))
            # one entry per rank — including the wedged one, whose
            # publisher thread answered while its main thread slept
            assert manifest["ranks_collected"] == [0, 1]
            assert manifest["missing"] == []
            stacks1 = open(
                os.path.join(bundle, "rank1-stacks.txt")).read()
            assert "MainThread" in stacks1
            flight1 = json.load(
                open(os.path.join(bundle, "rank1-flight.json")))
            kinds = {e["kind"] for e in flight1["flight"]["events"]}
            assert "fault.injected" in kinds   # the delay that wedged it
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
            agg.stop()
            store.close()
