"""Request tracing + roofline cost model tests (ISSUE 11).

Covers the new observability layer end to end, short of a live fleet
(tests/test_router.py holds the SIGKILL+failover merged-trace contract):

- ``telemetry.cost``: the jaxpr FLOPs/bytes walk (exact on dot_general,
  within 10% of an analytic hand-count on the llama test config's decode
  and prefill traces), the trace registry, and the roofline math.
- ``telemetry.reqtrace``: wire serialization, watermark draining with the
  engine-label filter, and the per-request Chrome merge (string-labeled
  rows through the generalized ``cluster.merge_traces``).
- Exemplars: trace ids on histogram buckets (OpenMetrics suffix, JSON
  snapshot) and on the SLO tracker's window p99s.
- Router propagation on fake replicas: trace ids in the pipe protocol,
  failover/replay spans, ``request_trace`` assembly.
- Tool tolerance: ``metrics_dump`` pretty-print/diff with exemplar
  annotations; ``trace_view`` waterfall rendering.
"""
import json
import time

import numpy as np
import pytest

import paddle_tpu
from paddle_tpu import telemetry
from paddle_tpu.telemetry import cost, reqtrace
from paddle_tpu.telemetry.metrics import MetricsRegistry
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.serving import LLMEngine, SamplingParams

pytestmark = pytest.mark.telemetry


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------

class TestJaxprCost:
    def test_dot_general_exact(self):
        import jax
        import jax.numpy as jnp

        a = jnp.zeros((8, 16), jnp.float32)
        b = jnp.zeros((16, 4), jnp.float32)
        est = cost.jaxpr_cost(jax.make_jaxpr(lambda x, y: x @ y)(a, b))
        assert est["matmul_flops"] == 2 * 8 * 16 * 4
        assert est["bytes"] == (8 * 16 + 16 * 4 + 8 * 4) * 4
        assert est["arithmetic_intensity"] == pytest.approx(
            est["flops"] / est["bytes"])

    def test_elementwise_and_reduce_counted(self):
        import jax
        import jax.numpy as jnp

        x = jnp.zeros((32, 8), jnp.float32)
        est = cost.jaxpr_cost(
            jax.make_jaxpr(lambda v: jnp.tanh(v * 2.0).sum())(x))
        # one mul + one tanh over 256 elements + a 256-element reduction
        assert est["elementwise_flops"] >= 3 * 256
        assert est["matmul_flops"] == 0

    def test_inner_jaxprs_recursed(self):
        import jax
        import jax.numpy as jnp

        inner = jax.jit(lambda x, y: x @ y)
        a = jnp.zeros((4, 4), jnp.float32)
        est = cost.jaxpr_cost(jax.make_jaxpr(
            lambda x, y: inner(x, y) + 1.0)(a, a))
        assert est["matmul_flops"] == 2 * 4 * 4 * 4   # found inside pjit

    def test_xla_cost_analysis_crosscheck(self):
        """Where the backend exposes compiled.cost_analysis(), its flops
        must agree with the jaxpr walk on a pure matmul (both count
        2*M*N*K)."""
        import jax.numpy as jnp

        a = jnp.ones((16, 32), jnp.float32)
        b = jnp.ones((32, 8), jnp.float32)

        def f(x, y):
            return x @ y

        ca = cost.xla_cost_analysis(f, a, b)
        if not ca or not ca.get("flops"):
            pytest.skip("backend exposes no cost_analysis")
        est = cost.estimate_fn_cost(f, a, b)
        assert est["matmul_flops"] == pytest.approx(ca["flops"], rel=0.5)

    def test_registry_fingerprint(self):
        est = {"flops": 10, "bytes": 5, "arithmetic_intensity": 2.0}
        cost.register_trace("t.callable", "B1", est, fingerprint=("a", 1))
        assert cost.lookup("t.callable", "B1", ("a", 1))["flops"] == 10
        assert cost.lookup("t.callable", "B1", ("other", 2)) is None
        assert cost.lookup("t.callable", "nope", ("a", 1)) is None

    def test_peaks_are_keyed_by_device_kind(self):
        v5e = cost.platform_peaks("TPU v5 lite")
        assert (v5e["flops_per_s"], v5e["bytes_per_s"]) == (197e12, 819e9)
        assert cost.platform_peaks()["device_kind"] == "cpu"
        with pytest.raises(ValueError, match="TPU v9 mega"):
            cost.platform_peaks("TPU v9 mega")   # unknown: no default

    def test_roofline_math(self):
        peaks = {"device_kind": "x", "flops_per_s": 100.0,
                 "bytes_per_s": 10.0}
        est = {"flops": 200.0, "bytes": 10.0}       # compute-bound: 2s
        assert cost.roofline_time_s(est, peaks) == pytest.approx(2.0)
        est = {"flops": 10.0, "bytes": 100.0}       # memory-bound: 10s
        assert cost.roofline_time_s(est, peaks) == pytest.approx(10.0)


def _tiny_engine(**kw):
    paddle_tpu.seed(0)
    cfg = llama_tiny(vocab=61, hidden=32, layers=2, heads=4, kv_heads=2,
                     inter=64, seq=64)
    return LLMEngine(LlamaForCausalLM(cfg), block_size=8, max_slots=2,
                     max_model_len=48, **kw)


def _matmul_hand_count(cfg, tokens_per_seq, batch, attn_ctx, lm_positions):
    """Analytic matmul-flop count of one llama forward: qkv + attention
    (scores + weighted sum over ``attn_ctx`` keys) + output proj + SwiGLU
    MLP per layer, plus the LM head over ``lm_positions`` positions."""
    H = cfg.hidden_size
    I = cfg.intermediate_size
    hd = cfg.head_dim
    heads = cfg.num_attention_heads
    qkv_out = (cfg.num_attention_heads + 2 * cfg.num_key_value_heads) * hd
    t = tokens_per_seq
    per_layer = (
        2 * t * H * qkv_out              # fused qkv projection
        + 4 * t * heads * hd * attn_ctx  # scores + prob@V
        + 2 * t * (heads * hd) * H       # o_proj
        + 2 * t * H * (2 * I)            # fused gate+up
        + 2 * t * I * H)                 # down
    total = cfg.num_hidden_layers * per_layer \
        + 2 * lm_positions * H * cfg.vocab_size
    return batch * total


class TestEngineCostModel:
    def test_decode_flops_within_10pct_of_hand_count(self):
        eng = _tiny_engine()
        eng.generate([[1, 2, 3, 4]], SamplingParams(max_new_tokens=4))
        est = eng._trace_costs[("decode", "decode")]
        cfg = eng.model.config
        # the fused decode trace: max_slots rows of 1 token each, paged
        # attention over the full padded table width
        hand = _matmul_hand_count(
            cfg, tokens_per_seq=1, batch=eng.max_slots,
            attn_ctx=eng.max_blocks * eng.block_size, lm_positions=1)
        assert abs(est["matmul_flops"] - hand) / hand < 0.10, \
            (est["matmul_flops"], hand)
        # total flops = matmuls + elementwise (norms/rope/softmax/silu);
        # the elementwise tail must exist but not dominate
        assert est["flops"] >= est["matmul_flops"]
        assert est["flops"] < 2.0 * hand

    def test_prefill_bucket_flops_within_10pct(self):
        eng = _tiny_engine()
        eng.generate([[1, 2, 3, 4]], SamplingParams(max_new_tokens=2))
        (bucket, est), = [((k, b), e) for (k, b), e
                          in eng._trace_costs.items()
                          if k == "prefill"][:1]
        P = int(bucket[1][1:])            # "P8" -> 8
        cfg = eng.model.config
        hand = _matmul_hand_count(cfg, tokens_per_seq=P, batch=1,
                                  attn_ctx=P, lm_positions=P)
        assert abs(est["matmul_flops"] - hand) / hand < 0.10, \
            (est["matmul_flops"], hand)

    def test_bytes_cover_weights_and_pool(self):
        eng = _tiny_engine()
        eng.generate([[1, 2, 3]], SamplingParams(max_new_tokens=2))
        est = eng._trace_costs[("decode", "decode")]
        # decode reads every weight and the pool (and writes the pool):
        # the modeled traffic must be at least params + pool
        floor = eng._params_bytes + eng._pool_bytes
        assert est["bytes"] >= floor

    def test_stats_roofline_block_and_gauge(self):
        eng = _tiny_engine()
        eng.generate([[1, 2, 3, 4], [5, 6, 7]],
                     SamplingParams(max_new_tokens=6))
        roof = eng.stats()["perf"]["roofline"]
        # counts, no share of a peak: that is the benchmark's to take
        assert set(roof) == {"prefill", "decode", "decode_ai"}
        assert set(roof["decode"]) == set(roof["prefill"]) == {"buckets"}
        assert roof["decode_ai"] > 0
        for bucket in (roof["decode"]["buckets"]["decode"],
                       *roof["prefill"]["buckets"].values()):
            assert set(bucket) == {"flops", "bytes", "arithmetic_intensity"}
            assert bucket["flops"] > 0 and bucket["bytes"] > 0
        text = telemetry.prometheus_text()
        assert "serving_roofline_frac" not in text
        assert "trace_flops" in text

    def test_trace_counters_unaffected_by_cost_walk(self):
        """The cost estimation traces the python callable once more via a
        fresh wrapper; the engine's own retrace counters must still count
        exactly one trace per bucket."""
        eng = _tiny_engine()
        eng.generate([[1, 2, 3, 4], [5, 6, 7]],
                     SamplingParams(max_new_tokens=4))
        assert eng.decode_traces == 1
        assert all(v == 1 for v in eng.prefill_traces.values())

    def test_fleet_replica_shares_estimate(self):
        """Same config + geometry -> the second engine resolves the cost
        from the registry instead of re-walking (fingerprint hit)."""
        e1 = _tiny_engine()
        e1.generate([[1, 2, 3]], SamplingParams(max_new_tokens=2))
        fp = e1._cost_fp
        assert cost.lookup("engine.decode", "decode", fp) is not None
        e2 = _tiny_engine()
        e2.generate([[1, 2, 3]], SamplingParams(max_new_tokens=2))
        assert e2._trace_costs[("decode", "decode")]["flops"] == \
            e1._trace_costs[("decode", "decode")]["flops"]


# ---------------------------------------------------------------------------
# exemplars
# ---------------------------------------------------------------------------

class TestExemplars:
    def test_histogram_exemplar_in_snapshot_and_text(self):
        reg = MetricsRegistry()
        h = reg.histogram("ttft_seconds", "ttft", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5, exemplar={"trace_id": "req-slow"})
        snap = reg.snapshot()
        ex = snap["ttft_seconds"]["series"][0]["exemplars"]
        assert ex["1"]["labels"] == {"trace_id": "req-slow"}
        assert ex["1"]["value"] == 0.5
        text = reg.prometheus_text()
        assert '# {trace_id="req-slow"} 0.5' in text
        # buckets without exemplars keep the plain exposition
        assert 'ttft_seconds_bucket{le="0.1"} 1\n' in text

    def test_no_exemplar_means_unchanged_exposition(self):
        reg = MetricsRegistry()
        h = reg.histogram("h_seconds", buckets=(1.0,))
        h.observe(0.5)
        assert "#" not in reg.prometheus_text().replace("# TYPE", "")
        assert "exemplars" not in reg.snapshot()["h_seconds"]["series"][0]

    def test_slo_p99_exemplar_names_the_culprit(self):
        tr = telemetry.SLOTracker(ttft_slo_s=1.0, engine_label="ex0")
        for i in range(20):
            tr.record_finished(ttft=0.01, tpot=0.001, queue_time=0.0,
                               tokens=4, trace_id=f"req-fast-{i}")
        tr.record_finished(ttft=5.0, tpot=0.002, queue_time=0.0,
                           tokens=4, trace_id="req-culprit")
        s = tr.summary()
        assert s["exemplars"]["ttft_p99"] == "req-culprit"
        assert s["exemplars"]["tpot_p99"] is not None


# ---------------------------------------------------------------------------
# wire format + merge
# ---------------------------------------------------------------------------

class TestReqtraceWire:
    def test_drain_watermark_and_engine_filter(self):
        tr = telemetry.tracer()
        tr.emit("plain", 0.0, 1.0, attrs={})                 # no context
        tr.emit("mine", 0.0, 1.0,
                attrs={"trace_id": "req-a", "engine": "7"})
        tr.emit("other", 0.0, 1.0,
                attrs={"trace_id": "req-a", "engine": "8"})
        spans, wm = reqtrace.drain_request_spans(0, engine_label="7")
        names = [s["name"] for s in spans]
        assert "mine" in names and "other" not in names
        assert "plain" not in names
        # watermark advances past everything seen, matching or not
        spans2, wm2 = reqtrace.drain_request_spans(wm, engine_label="7")
        assert spans2 == [] and wm2 == wm

    def test_wire_spans_unix_stamped(self):
        t0 = time.monotonic()
        with telemetry.span("w.op", trace_id="req-w"):
            time.sleep(0.01)
        s = [s for s in telemetry.tracer().spans()
             if s.attrs.get("trace_id") == "req-w"][-1]
        w = reqtrace.span_to_wire(s)
        assert abs(w["t0_unix"] - time.time()) < 60       # unix scale
        assert w["t1_unix"] - w["t0_unix"] >= 0.009
        assert reqtrace.wire_trace_ids(w) == ("req-w",)
        assert reqtrace.wire_trace_ids(
            {"attrs": {"trace_ids": ["a", "b"]}}) == ("a", "b")
        del t0

    def test_merge_request_trace_rows_and_orphans(self, tmp_path):
        base = time.time()

        def w(name, t0, t1, span_id=None, parent=None, **attrs):
            return {"name": name, "t0_unix": base + t0, "t1_unix": base + t1,
                    "span_id": span_id, "parent_id": parent,
                    "attrs": {"trace_id": "req-m", **attrs}}

        sources = {
            "gateway": [w("router.submit", 0.0, 0.001, span_id=1),
                        w("router.failover", 0.5, 0.501, span_id=2,
                          from_replica="r0", to_replica="r1")],
            "r0": [w("request", 0.0, 0.5, span_id=10),
                   w("prefill", 0.01, 0.2, span_id=11, parent=10)],
            "r1": [w("request", 0.5, 1.0, span_id=10)],
        }
        out = str(tmp_path / "merged.json")
        doc = reqtrace.merge_request_trace(
            "req-m", sources, out_path=out,
            meta={"failovers": 1, "replicas": ["r0", "r1"]})
        rows = {e["args"]["name"] for e in doc["traceEvents"]
                if e.get("ph") == "M" and e["name"] == "process_name"}
        assert rows == {"gateway", "r0", "r1"}
        assert doc["otherData"]["trace_id"] == "req-m"
        assert doc["otherData"]["failovers"] == 1
        # rows get distinct pids; parents resolve within their row
        by_pid = {}
        for e in doc["traceEvents"]:
            if e.get("ph") == "X":
                by_pid.setdefault(e["pid"], set()).add(
                    e["args"].get("span_id"))
        assert len(by_pid) == 3
        for e in doc["traceEvents"]:
            if e.get("ph") == "X" and e["args"].get("parent_id") is not None:
                assert e["args"]["parent_id"] in by_pid[e["pid"]]
        assert json.load(open(out))["otherData"]["trace_id"] == "req-m"

    def test_cluster_merge_still_takes_int_ranks(self, tmp_path):
        from paddle_tpu.telemetry.cluster import merge_traces

        t = {"traceEvents": [{"ph": "X", "name": "s", "pid": 0, "tid": 1,
                              "ts": 0.0, "dur": 5.0}],
             "otherData": {"epoch_unix": 100.0}}
        doc = merge_traces({0: t, 1: dict(t, otherData={
            "epoch_unix": 101.0})})
        names = {e["args"]["name"] for e in doc["traceEvents"]
                 if e.get("ph") == "M" and e["name"] == "process_name"}
        assert names == {"rank 0", "rank 1"}
        # rank 1's epoch is 1s later: its event is shifted by +1e6 us
        ts = sorted(e["ts"] for e in doc["traceEvents"]
                    if e.get("ph") == "X")
        assert ts == [0.0, 1e6]


# ---------------------------------------------------------------------------
# router propagation (fake replicas)
# ---------------------------------------------------------------------------

class _FakeRep:
    kind = "fake"

    def __init__(self, rid):
        self.rid = rid
        from paddle_tpu.serving import ReplicaState

        self.state = ReplicaState.HEALTHY
        self.stats = {"slo": {"shed": False}}
        self.last_heartbeat = time.monotonic()
        self.pid = 0
        self.sent = []
        self.alive = True
        self._on_event = None

    def start(self, on_event):
        self._on_event = on_event
        from paddle_tpu.serving import ReplicaState

        self.state = ReplicaState.HEALTHY

    def send(self, cmd):
        if not self.alive:
            raise BrokenPipeError(self.rid)
        self.sent.append(cmd)

    def stop(self, graceful=True, timeout=0):
        pass

    def emit_tokens(self, gid, toks, start=0):
        for i, t in enumerate(toks, start=start):
            self._on_event(self, {"ev": "token", "gid": gid,
                                  "tok": t, "i": i})

    def emit_done(self, gid, state="finished", reason="length"):
        self._on_event(self, {"ev": "done", "gid": gid, "state": state,
                              "reason": reason, "error": None, "n": 0})

    def emit_spans(self, spans):
        self._on_event(self, {"ev": "stats",
                              "stats": {"slo": {"shed": False}},
                              "spans": spans})


def _fake_router(n=2):
    from paddle_tpu.serving import FleetRouter

    reps = [_FakeRep(f"f{i}") for i in range(n)]
    router = FleetRouter(reps, affinity_block_size=4)
    for r in reps:
        r.start(router._on_event)
    return router, reps


class TestRouterPropagation:
    def test_trace_id_rides_the_pipe_protocol(self):
        router, reps = _fake_router()
        rr = router.submit([1, 2, 3, 4, 5], SamplingParams(),
                           trace_id="req-pipe")
        add = [c for c in router.replicas[rr.replica].sent
               if c["op"] == "add"][-1]
        assert add["trace_id"] == "req-pipe"
        assert rr.trace_id == "req-pipe"
        # without one the router mints
        rr2 = router.submit([9, 8, 7, 6, 5], SamplingParams())
        assert rr2.trace_id and rr2.trace_id != rr.trace_id

    def test_heartbeat_spans_absorbed_by_trace_id(self):
        router, reps = _fake_router()
        rr = router.submit([1, 2, 3, 4, 5], SamplingParams())
        rep = router.replicas[rr.replica]
        now = time.time()
        rep.emit_spans([
            {"name": "prefill", "t0_unix": now, "t1_unix": now + 0.1,
             "span_id": 5, "parent_id": None,
             "attrs": {"trace_id": rr.trace_id}},
            {"name": "engine.decode", "t0_unix": now, "t1_unix": now + 0.2,
             "span_id": 6, "parent_id": None,
             "attrs": {"trace_ids": [rr.trace_id, "req-other"]}},
            {"name": "stranger", "t0_unix": now, "t1_unix": now + 0.1,
             "span_id": 7, "parent_id": None,
             "attrs": {"trace_id": "req-unknown"}},
        ])
        assert [s["name"] for s in rr.remote_spans] == \
            ["prefill", "engine.decode"]
        assert all(s["replica"] == rep.rid for s in rr.remote_spans)

    def test_failover_spans_and_request_trace(self):
        router, reps = _fake_router()
        rr = router.submit([1, 2, 3, 4, 5], SamplingParams())
        a = router.replicas[rr.replica]
        b = [r for r in reps if r.rid != a.rid][0]
        a.emit_tokens(rr.gid, [10, 11, 12])
        router._mark_unhealthy(a, "test death")
        assert rr.replica == b.rid and rr.suppress == 3
        b.emit_tokens(rr.gid, [10, 11, 12, 13])   # replay + continue
        b.emit_done(rr.gid)
        doc = router.request_trace(rr.gid)
        rows = {e["args"]["name"] for e in doc["traceEvents"]
                if e.get("ph") == "M" and e["name"] == "process_name"}
        # both hops exist even though the fakes streamed no spans: the
        # dead hop is synthesized from the dispatch ledger
        assert {a.rid, b.rid, "gateway"} <= rows
        names = [e["name"] for e in doc["traceEvents"]
                 if e.get("ph") == "X"]
        assert "router.failover" in names
        assert "router.replay_suppressed" in names
        fo = [e for e in doc["traceEvents"]
              if e.get("ph") == "X" and e["name"] == "router.failover"][0]
        assert fo["args"]["replay_suppressed"] == 3
        assert fo["args"]["from_replica"] == a.rid
        assert doc["otherData"]["replicas"] == [a.rid, b.rid]

    def test_find_request_by_all_keys(self):
        router, _ = _fake_router()
        rr = router.submit([1, 2, 3, 4, 5], SamplingParams())
        assert router.find_request(rr.gid) is rr
        assert router.find_request(str(rr.gid)) is rr
        assert router.find_request(f"cmpl-{rr.gid}") is rr
        assert router.find_request(rr.trace_id) is rr
        assert router.find_request("cmpl-9999") is None
        with pytest.raises(KeyError):
            router.request_trace("req-nope")

    def test_placement_split_in_stats(self):
        router, _ = _fake_router()
        for _ in range(4):
            router.submit(list(np.random.randint(0, 50, 9)),
                          SamplingParams())
        st = router.stats()
        assert st["affinity_hits"] + st["p2c_placements"] >= 4


# ---------------------------------------------------------------------------
# tool tolerance
# ---------------------------------------------------------------------------

class TestToolTolerance:
    def _snap(self, with_exemplar=True, count=3):
        s = {"labels": {"engine": "0"},
             "buckets": {"0.1": 1, "1": count}, "sum": 0.7, "count": count,
             "mean": 0.7 / count}
        if with_exemplar:
            s["exemplars"] = {"1": {"labels": {"trace_id": "req-p99"},
                                    "value": 0.5, "ts": 1690000000.0}}
        return {"__meta__": {"wall_time": 100.0 + count},
                "serving_ttft_seconds": {
                    "type": "histogram", "help": "", "labels": ["engine"],
                    "series": [s]}}

    def test_pretty_print_shows_exemplar(self):
        import sys
        sys.path.insert(0, ".")
        from tools.metrics_dump import format_snapshot

        out = format_snapshot(self._snap())
        assert "serving_ttft_seconds" in out
        assert "ex:trace_id=req-p99" in out
        # and a snapshot WITHOUT exemplars renders identically to before
        assert "ex:" not in format_snapshot(self._snap(with_exemplar=False))

    def test_diff_tolerates_exemplars(self):
        import sys
        sys.path.insert(0, ".")
        from tools.metrics_dump import format_diff

        out = format_diff(self._snap(count=3), self._snap(count=5))
        assert "serving_ttft_seconds" in out
        assert "+2" in out

    def test_real_registry_snapshot_roundtrips_through_dump(self):
        import sys
        sys.path.insert(0, ".")
        from tools.metrics_dump import format_diff, format_snapshot

        reg = MetricsRegistry()
        h = reg.histogram("rt_seconds", buckets=(0.1, 1.0))
        h.observe(0.5, exemplar={"trace_id": "req-x"})
        snap = json.loads(json.dumps(reg.snapshot()))
        assert "ex:trace_id=req-x" in format_snapshot(snap)
        assert format_diff(snap, snap)     # no crash, no changed series

    def test_trace_view_renders_waterfall(self, capsys):
        import sys
        sys.path.insert(0, ".")
        from tools import trace_view

        base = time.time()
        doc = reqtrace.merge_request_trace("req-v", {
            "gateway": [{"name": "router.submit", "t0_unix": base,
                         "t1_unix": base + 0.001, "span_id": 1,
                         "parent_id": None,
                         "attrs": {"trace_id": "req-v"}}],
            "r0": [{"name": "queued", "t0_unix": base,
                    "t1_unix": base + 0.01, "span_id": 2,
                    "parent_id": None, "attrs": {"trace_id": "req-v"}},
                   {"name": "prefill", "t0_unix": base + 0.01,
                    "t1_unix": base + 0.11, "span_id": 3,
                    "parent_id": None, "attrs": {"trace_id": "req-v"}},
                   {"name": "decode", "t0_unix": base + 0.11,
                    "t1_unix": base + 0.31, "span_id": 4,
                    "parent_id": None, "attrs": {"trace_id": "req-v"}}],
        }, meta={"gid": 3, "state": "finished", "replicas": ["r0"]})
        out = trace_view.render(doc)
        assert "request trace req-v" in out
        assert "prefill" in out and "decode" in out
        assert "phases:" in out
        assert "queue=10.0ms" in out
        assert "decode=200.0ms" in out

    def test_trace_view_cli_reads_file(self, tmp_path, capsys):
        import sys
        sys.path.insert(0, ".")
        from tools import trace_view

        base = time.time()
        doc = reqtrace.merge_request_trace("req-c", {
            "gateway": [{"name": "router.submit", "t0_unix": base,
                         "t1_unix": base + 0.001, "span_id": 1,
                         "parent_id": None,
                         "attrs": {"trace_id": "req-c"}}]})
        p = tmp_path / "t.json"
        p.write_text(json.dumps(doc))
        assert trace_view.main([str(p)]) == 0
        assert "req-c" in capsys.readouterr().out
