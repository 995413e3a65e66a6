"""Phase spans that tile the serving loop, engine clocks that end at the
result, and the front-door delay / token relay counted where they end
(docs/OBSERVABILITY.md "Phase spans")."""
import queue
import threading
import time

import numpy as np
import pytest

import paddle_tpu
from paddle_tpu import telemetry
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.serving import (FleetRouter, LLMEngine, LocalReplica,
                                SamplingParams)
from paddle_tpu.telemetry import reqtrace

# one iteration of LocalReplica._drive without an admission, phase by
# phase, in the steady state of the decode pipeline: a step is dispatched,
# then the wait and the emit are of the step dispatched an iteration ago.
# engine.overlap (what needs no result of the step just dispatched, booked
# while the device runs it), the *_wait spans and replica.idle are left out
# of host-time sums.
PLAIN = ["replica.inbox", "engine.schedule", "engine.schedule",
         "engine.assemble", "engine.upload", "engine.decode",
         "engine.decode_wait", "engine.emit", "engine.account",
         "engine.overlap", "engine.account", "replica.sweep"]
# what an admission puts between the two engine.schedule spans (its
# dispatch), and behind the emit of the step before (its first token's read)
ADMIT_OUT = ["engine.prefill", "engine.overlap"]
ADMIT_BACK = ["engine.prefill_wait", "engine.emit", "engine.account"]
ORDER = set(PLAIN) | set(ADMIT_OUT) | set(ADMIT_BACK) | {"replica.idle"}


def _engine(late_s=0.0, **kw):
    """A tiny engine; with ``late_s``, one that is warm and whose decode
    step's tokens reach the host that much later (a device that takes its
    time, which the CPU at this width does not)."""
    paddle_tpu.seed(0)
    cfg = llama_tiny(vocab=61, hidden=32, layers=2, heads=4, kv_heads=2,
                     inter=64, seq=64)
    eng = LLMEngine(LlamaForCausalLM(cfg), block_size=8, max_slots=2,
                    max_model_len=48, **kw)
    if late_s:
        eng.generate([[1, 2, 3]], SamplingParams(max_new_tokens=3))
        _late(eng, late_s, ndim=1)
    return eng


def _late(eng, delay, ndim):
    """The readback of a decode step's tokens (``ndim`` 1) or of a prefill's
    first token (0) takes ``delay`` seconds: ``LLMEngine._fetch`` is where
    the host waits for a step's result."""
    real = eng._fetch

    def fetch(x):
        if np.ndim(x) == ndim:
            time.sleep(delay)
        return real(x)

    eng._fetch = fetch


def _iterations(spans):
    """Split one thread's phase spans, in time order, into iterations of the
    driver loop: from its ``replica.inbox`` (or the ``replica.idle`` before
    it) to its ``replica.sweep``. The warm-up's spans, before the loop,
    belong to none."""
    its, cur = [], None
    for s in sorted(spans, key=lambda s: s.t0):
        if s.name == "replica.idle" or (s.name == "replica.inbox"
                                        and not cur):
            cur = cur or []
        if cur is None:
            continue
        cur.append(s)
        if s.name == "replica.sweep":
            its.append(cur)
            cur = None
    return its


class TestPhaseSpansTileTheLoop:
    @pytest.fixture(scope="class")
    def iterations(self):
        telemetry.tracer().clear()
        events = queue.Queue()
        # a step of 20 ms, as a device's is: the few hundred us between the
        # spans of an iteration stay a small share of it on a loaded host
        rep = LocalReplica("r0", lambda: _engine(late_s=0.02))
        rep.start(lambda r, ev: events.put(ev))
        for gid in (1, 2):
            rep.send({"op": "add", "gid": gid, "prompt": [3, 1, 4, 1, 5],
                      "sampling": {"max_new_tokens": 12}, "trace_id": None})
        done = 0
        while done < 2:
            done += events.get(timeout=120)["ev"] == "done"
        tid = rep._thread.ident % 1_000_000
        rep.stop()
        assert not rep._thread.is_alive()
        spans = [s for s in telemetry.tracer().spans()
                 if s.tid == tid and s.name in ORDER]
        assert {s.name for s in telemetry.tracer().spans()
                if s.name.startswith(("engine.", "replica."))} <= ORDER
        return _iterations(spans)

    def test_flat_disjoint_and_in_order(self, iterations):
        decode = [it for it in iterations
                  if any(s.name == "engine.decode" for s in it)]
        assert len(decode) >= 10
        for it in iterations:
            for a, b in zip(it, it[1:]):
                assert a.t1 <= b.t0, (a, b)          # never nested
            assert all(s.parent_id is None for s in it)
        plain = [it for it in decode
                 if not any(s.name == "engine.prefill" for s in it)]
        assert len(plain) >= 8
        for it in plain:
            assert [s.name for s in it] == PLAIN
        # an admission puts its prefill's dispatch before the decode's
        # assembly, and the wait for its first token, that token's emit and
        # its booking behind the emit of the step before; the iteration
        # that fills the pipeline has no step before to wait for
        admit = [it for it in decode
                 if any(s.name == "engine.prefill" for s in it)]
        assert admit
        for i, it in enumerate(admit):
            names = [s.name for s in it if s.name != "replica.idle"]
            n = names.count("engine.prefill")
            before = PLAIN[6:9] if "engine.decode_wait" in names else []
            assert bool(before) == (i > 0)
            assert names == (PLAIN[:2] + ADMIT_OUT * n + PLAIN[2:6] + before
                             + ADMIT_BACK * n + PLAIN[9:])

    def test_the_wait_is_for_the_step_dispatched_an_iteration_ago(
            self, iterations):
        """Every ``engine.decode`` ends before the ``engine.decode_wait``
        of its own iteration begins, which is the wait for the step before:
        there is one wait fewer than dispatches in the iterations that
        dispatch, and the last step's wait is in an iteration of its own."""
        decode = [it for it in iterations
                  if any(s.name == "engine.decode" for s in it)]
        waits = [it for it in iterations
                 if any(s.name == "engine.decode_wait" for s in it)]
        assert len(waits) == len(decode)
        assert waits[0] is decode[1] and waits[-1] is not decode[-1]
        tail = [s.name for s in waits[-1] if s.name != "replica.idle"]
        assert tail == PLAIN[:4] + PLAIN[6:9] + PLAIN[10:]

    def test_spans_cover_the_iteration(self, iterations):
        decode = [it for it in iterations
                  if any(s.name == "engine.decode" for s in it)]
        # by the median iteration: a thread descheduled between two spans
        # (the tests run six at a time) is one iteration's gap, not the loop's
        shares = sorted(sum(s.duration for s in it) / (it[-1].t1 - it[0].t0)
                        for it in decode)
        assert shares[len(shares) // 2] >= 0.95, shares

    def test_phase_spans_carry_no_request_context(self, iterations):
        for it in iterations:
            for s in it:
                if s.name not in ("engine.prefill", "engine.decode"):
                    assert not reqtrace._carries_context(s.attrs)


class TestClocksEndAtTheResult:
    def test_late_result_is_in_the_clocks_and_trips_the_watchdog(self):
        eng = _engine()
        eng.generate([[1, 2, 3]], SamplingParams(max_new_tokens=3))
        eng.watchdog_timeout_s = 0.03           # the step is compiled now
        eng.generate([[1, 2, 3]], SamplingParams(max_new_tokens=3))
        assert eng.watchdog_trips == 0 and 0 < eng.last_decode_s < 0.03
        h = eng._m.decode_step
        n0, sum0 = h.count, h.sum
        _late(eng, 0.05, ndim=1)
        telemetry.tracer().clear()
        outs = eng.generate([[1, 2, 3]], SamplingParams(max_new_tokens=4))
        assert len(outs[0]) == 4
        steps = h.count - n0
        assert steps == 3
        assert eng.last_decode_s >= 0.05
        assert h.sum - sum0 >= 0.05 * steps
        assert eng.watchdog_trips == steps
        assert eng.stats()["watchdog_trips"] == steps
        # the dispatch span stays short; the wait is where the time went
        tr = telemetry.tracer()
        assert all(s.duration >= 0.05 for s in tr.find("engine.decode_wait"))
        assert all(s.duration < 0.03 for s in tr.find("engine.decode"))
        tl = eng.stats()["perf"]["decode_step"]
        assert tl["phases"]["wait"]["p99"] >= 0.05
        assert tl["step_s"]["p99"] >= 0.05

    def test_prefill_wall_ends_at_the_first_token(self, monkeypatch):
        """A bucket's first trace hands its wall time to the compile
        watcher, and every prefill's wait is the ``engine.prefill_wait``
        span: both hold the first token's readback, which a clock that
        ended at dispatch would leave out."""
        eng = _engine()
        walls = []
        record = eng._watcher.record_call

        def record_call(name, signature, wall_s=None, cost=None):
            if name == "engine.prefill":
                walls.append(wall_s)
            return record(name, signature, wall_s=wall_s, cost=cost)

        monkeypatch.setattr(eng._watcher, "record_call", record_call)
        _late(eng, 0.04, ndim=0)
        tr = telemetry.tracer()
        dispatches = []
        for prompt in ([1, 2, 3], [4, 5, 6]):   # first trace, then steady
            tr.clear()
            eng.generate([prompt], SamplingParams(max_new_tokens=1))
            (dispatch,), (wait,) = (tr.find("engine.prefill"),
                                    tr.find("engine.prefill_wait"))
            assert wait.duration >= 0.04
            dispatches.append(dispatch.duration)
        first, steady = walls
        # the first trace's wall: its dispatch (the compile) and the wait
        assert first >= dispatches[0] + 0.04
        assert steady is None       # a warm bucket books no compile time


class TestFrontDoor:
    def test_admit_delay_inbox_wait_and_token_stamp(self):
        telemetry.tracer().clear()
        events = queue.Queue()
        rep = LocalReplica("r0", _engine)
        rep.start(lambda r, ev: events.put(ev))
        now = telemetry.mono_to_unix(time.monotonic())
        rep.send({"op": "add", "gid": 5, "prompt": [3, 1, 4],
                  "sampling": {"max_new_tokens": 2}, "trace_id": "req-fd",
                  "t_front_unix": now - 0.25, "t_sent_unix": now - 0.10})
        toks = []
        while True:
            ev = events.get(timeout=120)
            if ev["ev"] == "token":
                toks.append(ev)
            if ev["ev"] == "done":
                break
        h = rep.engine._m.admit_delay
        rep.stop()
        assert h.count == 1 and 0.25 <= h.sum < 60
        (wait,) = telemetry.tracer().find("replica.inbox_wait")
        assert wait.attrs["trace_id"] == "req-fd" and wait.attrs["gid"] == 5
        assert 0.10 <= wait.duration < 60
        (queued,) = telemetry.tracer().find("queued")
        assert abs(wait.t1 - queued.t0) < 0.05    # the hop before `queued`
        assert len(toks) == 2
        for ev in toks:
            assert now <= ev["t"] <= telemetry.mono_to_unix(time.monotonic())

    def test_gateway_observes_the_relay_of_streamed_tokens(self):
        import http.client
        import json

        from paddle_tpu.serving import Gateway

        rep = LocalReplica("r0", _engine, warmup=[1, 2, 3])
        router = FleetRouter([rep], probe_timeout_s=30).start(
            wait_healthy_s=120)
        gw = Gateway(router).start()
        try:
            relay = gw._m.relay
            admit = rep.engine._m.admit_delay
            n0, a0 = relay.count, admit.count
            c = http.client.HTTPConnection(gw.host, gw.port, timeout=120)
            c.request("POST", "/v1/completions", json.dumps(
                {"prompt": [3, 1, 4, 1, 5], "max_tokens": 6,
                 "stream": True}), {"Content-Type": "application/json"})
            body = c.getresponse().read().decode()
            assert body.count("token_ids") == 6
            # every live token is observed once; its relay is short and
            # never negative (one clock on both ends here)
            assert 1 <= relay.count - n0 <= 6
            assert admit.count - a0 == 1 and 0 <= admit.sum < 60
            tid = json.loads(body.split("data: ")[-2])["paddle_tpu"][
                "trace_id"]
            # the replica's spans ride its next heartbeat to the router
            want = {"router.dispatch", "replica.inbox_wait", "queued"}
            deadline = time.monotonic() + 30
            while True:
                c = http.client.HTTPConnection(gw.host, gw.port, timeout=60)
                c.request("GET", f"/v1/traces/{tid}")
                doc = json.loads(c.getresponse().read())
                names = {e["name"] for e in doc["traceEvents"]
                         if e.get("ph") == "X"}
                if want <= names or time.monotonic() > deadline:
                    break
                time.sleep(0.05)
            assert want <= names
        finally:
            gw.stop()
            router.close()


class TestTracerCost:
    def test_drain_visits_only_the_new_spans_of_a_full_ring(
            self, monkeypatch):
        tr = telemetry.tracer()
        tr.clear()
        for _ in range(tr.capacity + 10):
            tr.emit("old", 0.0, 1.0, attrs={"trace_id": "req-old",
                                            "engine": "7"})
        assert len(tr.spans()) == tr.capacity and tr.dropped == 10
        _, mark = reqtrace.drain_request_spans(0, engine_label="7")
        visited = []
        real = reqtrace._carries_context
        monkeypatch.setattr(
            reqtrace, "_carries_context",
            lambda attrs: visited.append(attrs) or real(attrs))
        tr.emit("phase", 0.0, 1.0)                       # a phase span
        tr.emit("mine", 0.0, 1.0, attrs={"trace_id": "req-a", "engine": "7"})
        tr.emit("other", 0.0, 1.0, attrs={"trace_id": "req-a", "engine": "8"})
        spans, mark2 = reqtrace.drain_request_spans(mark, engine_label="7")
        assert [s["name"] for s in spans] == ["mine"]
        assert len(visited) == 3 and mark2 == mark + 3
        assert len(tr.spans()) == tr.capacity            # still full
        visited.clear()
        assert reqtrace.drain_request_spans(mark2, engine_label="7") == \
            ([], mark2)
        assert visited == []
        tr.clear()

    def test_a_span_that_ends_late_is_still_drained(self):
        """Spans enter the ring when they end, their ids are drawn when
        they start: the mark counts arrivals, so a long span that ends
        after shorter ones were drained is not skipped."""
        tr = telemetry.tracer()
        outer = telemetry.span("outer.op", trace_id="req-late")
        outer.__enter__()
        with telemetry.span("inner.op", trace_id="req-late"):
            pass
        first, mark = reqtrace.drain_request_spans(tr.appended - 1)
        assert [s["name"] for s in first] == ["inner.op"]
        outer.__exit__(None, None, None)
        second, _ = reqtrace.drain_request_spans(mark)
        assert [s["name"] for s in second] == ["outer.op"]
        assert second[0]["span_id"] < first[0]["span_id"]

    def test_concurrent_appends_lose_no_span(self):
        import sys

        tr = telemetry.Tracer(capacity=128)
        n_threads, per = 16, 400
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            seen, mark = [], 0

            def work(k):
                for i in range(per):
                    tr.emit(f"t{k}", 0.0, 1.0, attrs={"i": i})

            ts = [threading.Thread(target=work, args=(k,))
                  for k in range(n_threads)]
            for t in ts:
                t.start()
            while any(t.is_alive() for t in ts):
                new, mark = tr.since(mark)
                seen += new
            for t in ts:
                t.join(timeout=60)
                assert not t.is_alive()
            new, mark = tr.since(mark)
            seen += new
        finally:
            sys.setswitchinterval(interval)
        assert mark == tr.appended == n_threads * per
        assert tr.dropped == tr.appended - len(tr.spans())
        # a reader that keeps up sees each span at most once, in order
        assert len({s.span_id for s in seen}) == len(seen)
        for k in range(n_threads):
            mine = [s.attrs["i"] for s in seen if s.name == f"t{k}"]
            assert mine == sorted(mine)
