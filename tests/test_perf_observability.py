"""Performance-observability layer (ISSUE 9): recompilation watcher with
signature-diff explanations, per-tag memory accounting + leak sentinel,
step-time phase attribution with regression naming, the static-Executor
cache counters.

Everything here is deliberately cheap: the only jitted work is one tiny
static program and one tiny engine fleet (the heavyweight end-to-end
proof lives in ``tools/chaos_run.py --suite perf``).
"""
import os
import sys

import numpy as np
import pytest

import paddle_tpu
from paddle_tpu import static, telemetry
from paddle_tpu.telemetry import perf
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.serving import LLMEngine, RequestState, SamplingParams
from paddle_tpu.utils.faults import FaultPlan

pytestmark = pytest.mark.telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _sig(shape, name="tokens", dtype="int32"):
    return ((name, tuple(shape), dtype),)


# ---------------------------------------------------------------------------
# CompileWatcher
# ---------------------------------------------------------------------------

class TestCompileWatcher:
    def test_new_signature_counts_a_compile(self):
        w = perf.CompileWatcher(storm_threshold=99)
        assert w.record_call("f", _sig((8,)), wall_s=0.1) is True
        assert w.record_call("f", _sig((8,))) is False    # seen: no retrace
        assert w.record_call("f", _sig((16,)), wall_s=0.2) is True
        assert w.compiles("f") == 2
        assert w.compiles() == 2
        assert not w.storms()

    def test_storm_detection_and_latch(self):
        w = perf.CompileWatcher(storm_threshold=3, storm_window_s=60.0)
        telemetry.flight().clear()
        for n in (4, 8, 16, 32):
            w.record_call("decode", _sig((n,)))
        storms = w.storms()
        assert len(storms) == 1 and storms[0]["callable"] == "decode"
        assert storms[0]["distinct_signatures"] >= 3
        # latched: more churn must not fire a second storm counter event
        events = telemetry.flight().events("compile.storm")
        assert len(events) == 1
        w.record_call("decode", _sig((64,)))
        assert len(telemetry.flight().events("compile.storm")) == 1

    def test_explain_recompile_names_the_argument(self):
        """The signature-diff golden: which arg, which field, which
        values."""
        w = perf.CompileWatcher(storm_threshold=2)
        w.record_call("prefill", (("tokens", (8,), "int32"),
                                  ("table", (2,), "int32")))
        w.record_call("prefill", (("tokens", (16,), "int32"),
                                  ("table", (2,), "int32")))
        ex = w.explain("prefill")
        assert ex["callable"] == "prefill"
        assert ex["distinct_signatures"] == 2
        assert ex["changed_args"] == [
            {"arg": "tokens", "field": "shape", "before": (8,),
             "after": (16,)}]
        assert "tokens" in ex["text"] and "(8,) -> (16,)" in ex["text"]

    def test_explain_dtype_change_and_default_target(self):
        w = perf.CompileWatcher(storm_threshold=2)
        w.record_call("g", (("x", (4,), "float32"),))
        w.record_call("g", (("x", (4,), "bfloat16"),))
        ex = w.explain()           # no name: picks the churning callable
        assert ex["callable"] == "g"
        assert ex["changed_args"] == [
            {"arg": "x", "field": "dtype", "before": "float32",
             "after": "bfloat16"}]

    def test_wrap_times_only_new_signatures(self):
        import jax

        w = perf.CompileWatcher(storm_threshold=99)
        f = w.wrap(jax.jit(lambda x: x * 2), "double", argnames=("x",))
        f(np.ones(3, np.float32))
        f(np.ones(3, np.float32))
        f(np.ones(5, np.float32))
        assert w.compiles("double") == 2
        fam = telemetry.registry().get("xla_compile_seconds")
        assert fam.labels(callable="double").count == 2

    def test_abstract_signature_unwraps_tensors_and_scalars(self):
        t = paddle_tpu.to_tensor(np.zeros((2, 3), np.float32))
        sig = perf.abstract_signature([t, 7], argnames=("a", "b"))
        assert sig[0] == ("a", (2, 3), "float32")
        assert sig[1][0] == "b" and sig[1][1] == ()

    def test_dispatch_watching_opt_in(self):
        w = perf.compile_watcher()
        before = w.compiles()
        x = paddle_tpu.to_tensor(np.ones((3,), np.float32))
        (x + x)
        assert w.compiles() == before      # off by default: hot path clean
        perf.watch_dispatch(True)
        try:
            (x + x)
            names = [n for n in w.summary()["callables"]
                     if n.startswith("dispatch.")]
            assert names
        finally:
            perf.watch_dispatch(False)


# ---------------------------------------------------------------------------
# MemoryMonitor
# ---------------------------------------------------------------------------

class TestMemoryMonitor:
    def test_live_peak_and_attribution(self):
        mm = perf.MemoryMonitor()
        mm.add("params", 1000)
        mm.add("kv_pool", 600)
        mm.sub("kv_pool", 200)
        assert mm.live("params") == 1000
        assert mm.live("kv_pool") == 400
        assert mm.peak("kv_pool") == 600
        assert mm.live() == 1400 and mm.peak() == 1600
        at_peak = mm.peak_attribution()
        assert at_peak["total_peak_bytes"] == 1600
        assert at_peak["live_at_peak"] == {"params": 1000.0,
                                           "kv_pool": 600.0}

    def test_set_is_absolute_and_floors_at_zero(self):
        mm = perf.MemoryMonitor()
        mm.set("t", 50)
        mm.set("t", 30)
        assert mm.live("t") == 30 and mm.peak("t") == 50
        mm.sub("t", 100)
        assert mm.live("t") == 0

    def test_leak_sentinel_flags_monotonic_growth_once(self):
        telemetry.flight().clear()
        mm = perf.MemoryMonitor(leak_window=4)
        for i in range(4):
            mm.set("blocks", 100 * (i + 1))
            mm.note_step()
        assert "blocks" in mm.leak_report()
        assert len(telemetry.flight().events("memory.leak")) == 1
        mm.set("blocks", 600)
        mm.note_step()                    # still growing: flagged, no re-fire
        assert len(telemetry.flight().events("memory.leak")) == 1

    def test_steady_state_oscillation_not_flagged(self):
        mm = perf.MemoryMonitor(leak_window=4)
        for v in (100, 300, 100, 300, 100, 300, 100, 300):
            mm.set("blocks", v)
            mm.note_step()
        assert mm.leak_report() == {}

    def test_flat_watermark_not_flagged(self):
        mm = perf.MemoryMonitor(leak_window=4)
        for _ in range(6):
            mm.set("params", 1000)
            mm.note_step()
        assert mm.leak_report() == {}

    def test_device_stats_never_raises(self):
        st = perf.MemoryMonitor().device_stats()
        assert st is None or isinstance(st, dict)

    def test_timeline_is_bounded(self):
        mm = perf.MemoryMonitor(timeline_cap=8)
        for i in range(20):
            mm.set("x", i)
        tl = mm.timeline()
        assert len(tl) == 8 and tl[-1]["live"] == 19


# ---------------------------------------------------------------------------
# StepTimeline
# ---------------------------------------------------------------------------

class TestStepTimeline:
    @pytest.mark.parametrize("name", ["occupancy", "live_block_share"])
    def test_shares_reported_beside_the_times(self, name):
        tl = perf.StepTimeline("t_shares")
        tl.record_step(0.010, {})                  # a step without shares
        assert name not in tl.report()
        for v in (0.25, 0.5, 1.0):
            tl.record_step(0.010, {}, **{name: v})
        assert tl.report()[name] == {"mean": pytest.approx(1.75 / 3),
                                     "p50": 0.5}
        tl.clear()
        assert name not in tl.report()

    def test_sampled_step_share_is_one_number_over_the_window(self):
        tl = perf.StepTimeline("t_sampled", window=4)
        tl.record_step(0.010, {})                  # a step that did not say
        assert "sampled_step_share" not in tl.report()
        for flag in (False, False, True):
            tl.record_step(0.010, {}, sampled=flag)
        assert tl.report()["sampled_step_share"] == pytest.approx(1 / 3)
        for _ in range(4):                         # the window moves on
            tl.record_step(0.010, {}, sampled=False)
        assert tl.report()["sampled_step_share"] == 0.0
        tl.clear()
        assert "sampled_step_share" not in tl.report()

    def test_phase_math_and_other(self):
        tl = perf.StepTimeline("t1")
        tl.record_step(0.010, {"data": 0.002, "compute": 0.006})
        rep = tl.report()
        assert rep["steps"] == 1
        assert rep["phases"]["other"]["mean"] == pytest.approx(0.002)
        fracs = sum(p["frac"] for p in rep["phases"].values())
        assert fracs == pytest.approx(1.0)

    def test_percentiles(self):
        tl = perf.StepTimeline("t2", window=128)
        for v in range(1, 101):                 # 1..100 ms
            tl.record_step(v / 1000.0, {})
        rep = tl.report()
        assert rep["step_s"]["p50"] == pytest.approx(0.0505, abs=1e-3)
        assert rep["step_s"]["p99"] == pytest.approx(0.100, abs=2e-3)

    def test_regression_names_culprit_phase(self):
        telemetry.flight().clear()
        tl = perf.StepTimeline("t3", regress_factor=1.5, min_baseline=8)
        for _ in range(10):
            tl.record_step(0.010, {"data": 0.002, "compute": 0.007})
        assert tl.regressions == 0
        tl.record_step(0.050, {"data": 0.002, "compute": 0.047})
        assert tl.regressions == 1
        reg = tl.report()["last_regression"]
        assert reg["culprit"] == "compute"
        assert reg["baseline_s"] == pytest.approx(0.010)
        evs = telemetry.flight().events("step.regression")
        assert evs and evs[-1]["culprit"] == "compute"
        fam = telemetry.registry().get("step_regressions_total")
        assert fam.labels(timeline="t3", phase="compute").value == 1

    def test_within_baseline_never_regresses(self):
        tl = perf.StepTimeline("t4", regress_factor=1.5, min_baseline=8)
        for v in (10, 11, 9, 10, 12, 10, 9, 11, 10, 13, 12):   # noise
            tl.record_step(v / 1000.0, {})
        assert tl.regressions == 0

    def test_step_ctx_and_note_phase(self):
        tl = perf.step_timeline("t5")
        tl.clear()
        with tl.step():
            with tl.phase("data"):
                pass
            perf.note_phase("collective", 0.004)   # external attribution
        rep = tl.report()
        assert rep["steps"] == 1
        assert rep["phases"]["collective"]["mean"] == pytest.approx(0.004)


# ---------------------------------------------------------------------------
# static.Executor cache metrics + compile watching
# ---------------------------------------------------------------------------

class TestExecutorCacheMetrics:
    def test_hits_misses_and_watcher_signature(self):
        reg = telemetry.registry()
        prog = static.Program()
        # unique feed name: the watcher is process-global and feed
        # signatures from other suites' Executors must not collide
        with static.program_guard(prog):
            x = static.data("perf_x9", [None, 3], "float32")
            y = x * 2.0
        exe = static.Executor()
        hits0 = reg.counter("static_executor_cache_hits_total").value
        miss0 = reg.counter("static_executor_cache_misses_total").value
        w = perf.compile_watcher()

        feed = {"perf_x9": np.ones((2, 3), np.float32)}
        exe.run(prog, feed=feed, fetch_list=[y])
        exe.run(prog, feed=feed, fetch_list=[y])          # cache hit
        exe.run(prog, feed={"perf_x9": np.ones((4, 3), np.float32)},
                fetch_list=[y])                            # new shape
        assert reg.counter("static_executor_cache_hits_total").value \
            == hits0 + 1
        assert reg.counter("static_executor_cache_misses_total").value \
            == miss0 + 2
        assert exe._trace_count == 2                       # hook preserved
        sigs = [tuple(s) for s in w.signatures("static.Executor")]
        assert (("perf_x9", (2, 3), "float32"),) in sigs
        assert (("perf_x9", (4, 3), "float32"),) in sigs
        # the watcher can name the feed whose shape churned (the two runs
        # above are the last two distinct signatures recorded)
        ex = w.explain("static.Executor")
        assert any(c["arg"] == "perf_x9" for c in ex["changed_args"])


# ---------------------------------------------------------------------------
# engine integration: stats()["perf"] + memory tags
# ---------------------------------------------------------------------------

class TestEnginePerf:
    @pytest.fixture(scope="class")
    def served(self):
        paddle_tpu.seed(0)
        perf.memory_monitor().clear()
        cfg = llama_tiny(vocab=61, hidden=32, layers=2, heads=4, kv_heads=2,
                         inter=64, seq=64)
        eng = LLMEngine(LlamaForCausalLM(cfg), block_size=8, max_slots=2,
                        max_model_len=48)
        outs = eng.generate([[1, 2, 3, 4], [5, 6, 7]],
                            SamplingParams(max_new_tokens=4))
        return eng, outs

    def test_perf_block_shape(self, served):
        eng, outs = served
        assert all(len(o) == 4 for o in outs)
        p = eng.stats()["perf"]
        assert set(p) == {"compiles", "storms", "explain_recompile",
                          "decode_step", "memory", "roofline"}
        # the watcher is process-global (other suites' engines add their
        # own signatures), so assert THIS engine's exact signatures landed
        # rather than absolute counts: slots=2, max_blocks=48/8=6, and the
        # 3-4 token prompts bucket to one P=8 prefill trace
        w = perf.compile_watcher()
        assert (("tokens", (2,), "int32"),
                ("block_tables", (2, 6), "int32")) \
            in w.signatures("engine.decode")
        assert (("tokens", (8,), "int32"),
                ("block_table", (1,), "int32")) \
            in w.signatures("engine.prefill")
        assert p["compiles"]["callables"]["engine.decode"]["compiles"] >= 1
        assert p["decode_step"]["steps"] >= 3
        assert {"assemble", "upload", "dispatch", "wait", "emit"} <= \
            set(p["decode_step"]["phases"])
        # running slots / max_slots a step (the "decode" timeline is the
        # process's: other suites' engines are in its window too)
        occ = p["decode_step"]["occupancy"]
        assert 0.0 < occ["mean"] <= 1.0 and 0.0 < occ["p50"] <= 1.0

    def test_live_block_share_follows_the_contexts(self):
        """The share of the running slots' table entries that hold context:
        the kernel's context is the host's ctx + 1 (the token being written
        counts), so a 7-token prompt decodes at 8, 9, 10 tokens: 1, 2, 2 of
        48 / 8 = 6 blocks, whatever the idle slot holds."""
        paddle_tpu.seed(0)
        cfg = llama_tiny(vocab=61, hidden=32, layers=2, heads=4, kv_heads=2,
                         inter=64, seq=64)
        eng = LLMEngine(LlamaForCausalLM(cfg), block_size=8, max_slots=2,
                        max_model_len=48)
        eng._decode_tl.clear()          # the process's timeline: start clean
        eng.generate([[1, 2, 3, 4, 5, 6, 7]],
                     SamplingParams(max_new_tokens=4))
        live = eng.stats()["perf"]["decode_step"]["live_block_share"]
        assert live["mean"] == pytest.approx((1 + 2 + 2) / 3 / 6)
        assert live["p50"] == pytest.approx(2 / 6)
        eng.close()

    def test_memory_tags_registered(self, served):
        eng, _ = served
        tags = eng.stats()["perf"]["memory"]["tags"]
        assert tags["params"]["live_bytes"] > 0
        assert tags["kv_pool"]["live_bytes"] == eng.cache.pool.nbytes
        assert tags["kv_blocks"]["peak_bytes"] > 0
        assert tags["kv_blocks"]["live_bytes"] == 0      # drained: no leak
        assert tags["activations_estimate"]["peak_bytes"] > 0

    def test_close_releases_memory_tags(self, served):
        eng, _ = served
        mm = perf.memory_monitor()
        params_before = mm.live("params")
        eng.close()
        assert mm.live("params") == params_before - eng._params_bytes
        assert mm.live("kv_pool") == 0

    def test_compile_fault_isolated_to_one_request(self):
        paddle_tpu.seed(0)
        cfg = llama_tiny(vocab=61, hidden=32, layers=2, heads=4, kv_heads=2,
                         inter=64, seq=64)
        eng = LLMEngine(LlamaForCausalLM(cfg), block_size=8, max_slots=2,
                        max_model_len=48)
        with FaultPlan.parse("serving.compile:error@1"):
            eng.generate([[1, 2, 3, 4], [5, 6, 7]],
                         SamplingParams(max_new_tokens=3))
        failed = [r for r in eng.failed]
        assert len(failed) == 1 and failed[0].error is not None
        assert len(eng.finished) == 1
        assert all(r.state is RequestState.FINISHED for r in eng.finished)


# ---------------------------------------------------------------------------
# tools/metrics_dump.py
# ---------------------------------------------------------------------------

class TestMetricsDump:
    def test_gauge_diff_shows_delta(self, tmp_path):
        from tools.metrics_dump import format_diff
        a = {"__meta__": {"wall_time": 0.0},
             "g": {"type": "gauge", "help": "", "labels": [],
                   "series": [{"labels": {}, "value": 3.0}]}}
        b = {"__meta__": {"wall_time": 1.0},
             "g": {"type": "gauge", "help": "", "labels": [],
                   "series": [{"labels": {}, "value": 7.5}]}}
        out = format_diff(a, b)
        assert "3 -> 7.5" in out and "(+4.5)" in out
