"""What each token waited for (docs/OBSERVABILITY.md "Step-time
attribution"): every gap between a request's consecutive token reads is classed
by the device programs dispatched between them (the device runs them in
that order): ``drain`` if a drain came between, else ``prefill`` if a
prefill did, else ``plain``; every first token is split into queue, prep and
behind, which sum to the engine's TTFT."""
import time

import numpy as np
import pytest

import paddle_tpu
from paddle_tpu import telemetry
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.serving import LLMEngine, SamplingParams
from paddle_tpu.serving import engine as engine_mod
from paddle_tpu.serving.router import replica_stats
from paddle_tpu.serving.scheduler import RequestState

VOCAB = 61


@pytest.fixture(scope="module")
def model():
    paddle_tpu.seed(0)
    return LlamaForCausalLM(llama_tiny(vocab=VOCAB, hidden=32, layers=2,
                                       heads=4, kv_heads=2, inter=64, seq=64))


def _prompt(n, seed=0):
    return np.random.RandomState([seed, n]).randint(1, VOCAB, n).tolist()


def _greedy(n):
    return SamplingParams(max_new_tokens=n)


def _engine(model, **kw):
    kw = {"block_size": 8, "max_slots": 3, "max_model_len": 64, **kw}
    return LLMEngine(model, **kw)


def _steps(eng, n):
    for _ in range(n):
        eng.step()


def _class(row):
    return ("drain" if row[3] > 0 else "prefill" if row[1] > 0
            else "plain")


def _spy(eng):
    """Every booking in order, each with its request: ``("first", rid,
    (queue, prep, behind))`` or ``("gap", rid, class, prefills, padded,
    gap_s)``, read back from the rings as they were written."""
    log = []
    clock, prefill = eng._clock_decode, eng._book_prefill_token

    def rows_since(n0):
        return [eng._gap_ring[i % engine_mod._GAP_RING].copy()
                for i in range(n0, eng._gaps_booked)]

    def clock_decode(step, counters, read):
        n0 = eng._gaps_booked
        clock(step, counters, read)
        for slot, row in zip(read, rows_since(n0)):
            log.append(("gap", step.rows[slot].rid, _class(row), row[1],
                        row[2], row[0]))

    def book_prefill_token(p, t_read, first):
        n0, f0 = eng._gaps_booked, eng._first_tokens_booked
        prefill(p, t_read, first)
        for row in rows_since(n0):
            log.append(("gap", p.req.rid, _class(row), row[1], row[2],
                        row[0]))
        if eng._first_tokens_booked > f0:
            at = f0 % engine_mod._FIRST_TOKEN_RING
            log.append(("first", p.req.rid,
                        tuple(eng._first_token_ring[at])))

    eng._clock_decode = clock_decode
    eng._book_prefill_token = book_prefill_token
    return log


def _gaps(log, rid=None):
    return [e for e in log if e[0] == "gap" and rid in (None, e[1])]


def _every_token_after_the_first_is_one_gap(log, reqs):
    for r in reqs:
        assert len(_gaps(log, r.rid)) == len(r.output_tokens) - 1, r.rid


class TestClasses:
    def test_a_steady_batch_books_only_plain_gaps(self, model):
        """One request alone, then three admitted one after another while
        nothing else runs: no prefill lies between two reads of a row."""
        eng = _engine(model)
        log = _spy(eng)
        reqs = [eng.add_request(_prompt(5), _greedy(9))]
        eng.run()
        for n in (4, 9, 6):
            reqs.append(eng.add_request(_prompt(n, seed=n), _greedy(7)))
            eng.run()
        _every_token_after_the_first_is_one_gap(log, reqs)
        assert {e[2] for e in _gaps(log)} == {"plain"}
        tg = eng.stats()["perf"]["token_gaps"]
        assert tg["gaps"] == 8 + 3 * 6
        assert tg["plain"]["share"] == 1.0
        assert tg["prefill"]["share"] == tg["drain"]["share"] == 0.0
        assert tg["prefill"]["p50"] is None
        assert tg["prefill"]["prefills_mean"] is None
        assert 0 < tg["all"]["p50"] <= tg["all"]["p95"] <= tg["all"]["p99"]
        eng.close()

    def test_an_admission_mid_stream_is_in_the_next_gap_of_every_row(
            self, model):
        eng = _engine(model)
        log = _spy(eng)
        a = eng.add_request(_prompt(5), _greedy(14))
        b = eng.add_request(_prompt(9), _greedy(14))
        _steps(eng, 4)
        assert len(a.output_tokens) >= 2 and len(b.output_tokens) >= 2
        mark = len(log)
        c = eng.add_request(_prompt(11, seed=1), _greedy(6))
        eng.run()
        _every_token_after_the_first_is_one_gap(log, (a, b, c))
        # c's first token is read in the iteration that dispatched its
        # prefill, behind the gaps of the step dispatched before it (plain);
        # each running row's next gap holds it: one prefill, of c's bucket
        at = next(i for i, e in enumerate(log) if e[:2] == ("first", c.rid))
        assert {g[2] for g in _gaps(log[mark:at])} == {"plain"}
        for r in (a, b):
            mine = _gaps(log[at:], r.rid)
            assert mine[0][2:5] == ("prefill", 1, eng._bucket(11))
            assert {g[2] for g in mine[1:]} == {"plain"}
        # c's own second token holds only its own prefill, which is not
        # between its two reads: plain, as is everything after
        assert {g[2] for g in _gaps(log, c.rid)} == {"plain"}
        # before c: b was admitted behind a in one iteration, so a's
        # second token holds b's prefill; nothing else held one
        early = _gaps(log[:mark])
        assert [g[1] for g in early if g[2] == "prefill"] == [a.rid]
        tg = eng.stats()["perf"]["token_gaps"]
        assert tg["prefill"]["share"] == pytest.approx(3 / tg["gaps"])
        assert tg["prefill"]["prefills_mean"] == 1.0
        eng.close()

    def test_two_prompts_admitted_in_one_iteration_are_two_prefills(
            self, model):
        """Both prefills are dispatched between the running row's two
        reads; the two newcomers ask for one token each, so no gap of
        theirs is booked."""
        eng = _engine(model)
        log = _spy(eng)
        a = eng.add_request(_prompt(5), _greedy(10))
        _steps(eng, 3)
        b = eng.add_request(_prompt(7, seed=2), _greedy(1))
        c = eng.add_request(_prompt(13, seed=3), _greedy(1))
        eng.run()
        assert len(b.output_tokens) == len(c.output_tokens) == 1
        _every_token_after_the_first_is_one_gap(log, (a, b, c))
        held = [g for g in _gaps(log) if g[2] == "prefill"]
        assert len(held) == 1 and held[0][1] == a.rid
        pf = eng.stats()["perf"]["token_gaps"]["prefill"]
        assert pf["prefills_mean"] == 2.0
        assert pf["padded_tokens_mean"] == eng._bucket(7) + eng._bucket(13)
        assert pf["p50"] == pytest.approx(held[0][5], rel=1e-6)
        eng.close()

    def test_a_cancel_books_a_drain(self, model):
        eng = _engine(model)
        log = _spy(eng)
        a = eng.add_request(_prompt(5, seed=8), _greedy(12))
        b = eng.add_request(_prompt(9, seed=8), _greedy(12))
        _steps(eng, 3)
        assert eng._inflight is not None
        mark = len(log)
        assert eng.cancel(b.rid)
        assert b.state is RequestState.CANCELLED
        eng.run()
        _every_token_after_the_first_is_one_gap(log, (a, b))
        # the drain read a's token in flight (a gap like any other); a's
        # next gap is the one the drain lies in
        after = _gaps(log[mark:], a.rid)
        assert after[0][2] != "drain" and after[1][2] == "drain"
        assert {g[2] for g in after[2:]} == {"plain"}
        assert [g[2] for g in _gaps(log)].count("drain") == 1
        assert eng.stats()["perf"]["decode_step"]["drains"] == {"cancel": 1}
        eng.close()

    def test_a_preemption_books_a_drain_and_the_reprefill_is_one(
            self, model):
        eng = _engine(model, block_size=4, max_slots=3, max_model_len=32,
                      num_blocks=10)
        log = _spy(eng)
        reqs = [eng.add_request(_prompt(n, seed=3 + i), _greedy(14))
                for i, n in enumerate((7, 6, 7, 6))]
        eng.run()
        assert eng.stats()["num_preemptions"] >= 1
        _every_token_after_the_first_is_one_gap(log, reqs)
        for r in reqs:
            assert r.state is RequestState.FINISHED
            if r.num_preemptions:
                # the token its re-prefill samples closes a drain gap that
                # began at its last read before the preemption
                assert "drain" in {g[2] for g in _gaps(log, r.rid)}
        tg = eng.stats()["perf"]["token_gaps"]
        assert tg["drain"]["share"] > 0
        assert tg["drain"]["p50"] > 0
        eng.close()


class TestTheRings:
    def test_shares_sum_to_one_and_the_ring_keeps_its_size(
            self, model, monkeypatch):
        monkeypatch.setattr(engine_mod, "_GAP_RING", 16)
        eng = _engine(model)
        log = _spy(eng)
        a = eng.add_request(_prompt(5), _greedy(20))
        _steps(eng, 4)
        reqs = [a] + [eng.add_request(_prompt(n, seed=n), _greedy(9))
                      for n in (6, 10)]
        eng.run()
        _every_token_after_the_first_is_one_gap(log, reqs)
        assert len(_gaps(log)) == 19 + 8 + 8 > 16
        assert eng._gap_ring.shape == (16, 4)
        tg = eng.stats()["perf"]["token_gaps"]
        assert tg["gaps"] == 16
        assert sum(tg[c]["share"] for c in ("plain", "prefill", "drain")) \
            == pytest.approx(1.0)
        # the ring holds the last 16 gaps booked, written across its end
        last = sorted(g[5] for g in _gaps(log)[-16:])
        assert sorted(eng._gap_ring[:, 0].tolist()) == pytest.approx(last)
        eng.close()

    def test_first_token_parts_sum_to_the_engines_ttft(self, model):
        eng = _engine(model, max_slots=2)
        log = _spy(eng)
        reqs = [eng.add_request(_prompt(n, seed=n), _greedy(4))
                for n in (5, 9, 12, 7)]      # two wait for a slot
        eng.run()
        firsts = {e[1]: e[2] for e in log if e[0] == "first"}
        assert sorted(firsts) == sorted(r.rid for r in reqs)
        for r in reqs:
            queue, prep, behind = firsts[r.rid]
            assert min(queue, prep, behind) >= 0
            assert queue + prep + behind == pytest.approx(r.ttft, abs=1e-6)
            assert queue == pytest.approx(r.admit_time - r.arrival_time,
                                          abs=1e-9)
        # the last two queued behind the first two's whole streams
        assert max(firsts[r.rid][0] for r in reqs[2:]) > \
            max(firsts[r.rid][0] for r in reqs[:2])
        ft = eng.stats()["perf"]["first_token"]
        assert ft["first_tokens"] == 4
        for part in ("queue", "prep", "behind"):
            assert 0 <= ft[part]["p50"] <= ft[part]["p95"]
        eng.close()

    def test_telemetry_disabled_books_nothing(self, model):
        eng = _engine(model)
        telemetry.disable()
        try:
            a = eng.add_request(_prompt(5), _greedy(8))
            _steps(eng, 3)
            eng.add_request(_prompt(9), _greedy(8))
            eng.run()
        finally:
            telemetry.enable()
        assert len(a.output_tokens) == 8
        assert eng._gaps_booked == eng._first_tokens_booked == 0
        perf = eng.stats()["perf"]
        assert perf["token_gaps"]["gaps"] == 0
        assert perf["token_gaps"]["all"]["p95"] is None
        assert perf["first_token"]["first_tokens"] == 0
        eng.close()

    def test_the_heartbeat_computes_no_percentile(self, model, monkeypatch):
        eng = _engine(model)
        eng.generate([_prompt(5), _prompt(8)], _greedy(5))

        def refuse(*a, **k):
            raise AssertionError("a percentile on the heartbeat's path")

        monkeypatch.setattr(eng, "_token_gaps_report", refuse)
        monkeypatch.setattr(eng, "_first_token_report", refuse)
        beat = replica_stats(eng)
        assert "perf" not in beat and beat["generated_tokens"] == 10
        eng.close()

    def test_a_decode_steps_rows_are_booked_at_once(self, model):
        """The rows of a step are booked together: one gap and the same
        stamps' differences a row, and every slot's record takes the
        step's stamps."""
        eng = _engine(model, max_slots=64, max_model_len=32)
        eng._slot_last[:] = (1.0, 3, 96, 0)
        t = time.monotonic()
        eng._book_decode_gaps(list(range(64)), t, (5, 160))
        rows = eng._gap_ring[:64]
        assert eng._gaps_booked == 64
        np.testing.assert_allclose(rows[:, 1:], [[2, 64, 0]] * 64)
        np.testing.assert_allclose(rows[:, 0], t - 1.0, rtol=1e-6)
        assert (eng._slot_last == (t, 5, 160, 0)).all()
        eng.close()
