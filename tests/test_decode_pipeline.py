"""The decode loop as a pipeline of depth one (docs/SERVING.md "The pipelined
loop"): step N+1 is dispatched before step N's tokens are read, the tokens go
from step to step on the device, the host counts the token in flight. Every
served stream stays what ``naive_generate`` gives; anything but the common
case drains first; a step that fails on the device fails the rows of the
steps in flight and nothing else."""
import time

import numpy as np
import pytest

import paddle_tpu
from paddle_tpu import telemetry
from paddle_tpu.models import (LagunaForCausalLM, LlamaForCausalLM,
                               laguna_tiny, llama_tiny)
from paddle_tpu.serving import LLMEngine, SamplingParams, naive_generate
from paddle_tpu.serving.scheduler import DeadlineExceeded, RequestState
from paddle_tpu.utils.faults import FaultPlan

VOCAB = 61


def _llama():
    paddle_tpu.seed(0)
    return LlamaForCausalLM(llama_tiny(vocab=VOCAB, hidden=32, layers=2,
                                       heads=4, kv_heads=2, inter=64, seq=64))


def _laguna():
    """Sliding-window layers (window 8) beside full ones, sparse experts."""
    paddle_tpu.seed(11)
    return LagunaForCausalLM(laguna_tiny(seq=96, window=8))


def _falcon():
    """The toy Falcon-H1 on weights under which the carried state is most of
    the mixer's output: a state left behind in a slot would show."""
    from test_falcon_h1 import build, draw

    return build(draw(1))


MODELS = {"llama": _llama, "window": _laguna, "state": _falcon}


@pytest.fixture(scope="module")
def models():
    built = {}

    def get(name):
        if name not in built:
            built[name] = MODELS[name]()
        return built[name]

    return get


def _prompt(n, seed=0):
    return np.random.RandomState([seed, n]).randint(1, VOCAB, n).tolist()


_NAIVE = {}


def _naive(models, name, prompt, sp, eos=None):
    key = (name, tuple(prompt), sp.max_new_tokens, sp.temperature, sp.top_k,
           sp.top_p, sp.seed, eos)
    if key not in _NAIVE:
        _NAIVE[key] = naive_generate(models(name), prompt, sp,
                                     eos_token_id=eos)
    return _NAIVE[key]


def _engine(model, **kw):
    kw = {"block_size": 8, "max_slots": 3, "max_model_len": 64, **kw}
    return LLMEngine(model, **kw)


def _steps(eng, n):
    for _ in range(n):
        eng.step()


def _in_flight(eng):
    """Step until a decode step is dispatched and unread."""
    for _ in range(8):
        if eng._inflight is not None:
            return
        eng.step()
    raise AssertionError("no decode step in flight")


def _greedy(n):
    return SamplingParams(max_new_tokens=n)


# ---------------------------------------------------------------------------
# (a) the served streams are naive_generate's, token for token
# ---------------------------------------------------------------------------

class TestStreamsAreTheSerialOnes:
    @pytest.mark.parametrize("name", ["llama", "window", "state"])
    def test_requests_admitted_while_others_decode(self, models, name):
        eng = _engine(models(name))
        sp = _greedy(8)
        # two lengths: the uncached reference compiles every shape it meets
        prompts = [_prompt(n, seed=i) for i, n in enumerate((5, 11, 5, 11, 5))]
        reqs = [eng.add_request(prompts[0], sp)]
        _steps(eng, 3)
        reqs.append(eng.add_request(prompts[1], sp))
        _steps(eng, 2)
        reqs += [eng.add_request(p, sp) for p in prompts[2:]]
        eng.run()
        for p, r in zip(prompts, reqs):
            assert r.state is RequestState.FINISHED and r.in_flight == 0
            assert r.output_tokens == _naive(models, name, p, sp)
        step = eng.stats()["perf"]["decode_step"]
        assert step["drains"] == {}
        assert step["pipelined_step_share"] > 0.8
        assert eng.decode_traces == 1
        eng.close()

    @pytest.mark.parametrize("max_new", [1, 2, 3, 9])
    def test_finish_by_max_new_tokens(self, models, max_new):
        """A request whose dispatched count has reached its limit has no
        row in the next step: it gets exactly its tokens, and a length at
        ``max_model_len`` is never passed."""
        eng = _engine(models("llama"), max_model_len=16)
        sp = _greedy(max_new)
        long = SamplingParams(max_new_tokens=16 - 7)     # ends at the cap
        prompts = [_prompt(4), _prompt(7), _prompt(6)]
        reqs = [eng.add_request(prompts[0], sp),
                eng.add_request(prompts[1], long),
                eng.add_request(prompts[2], sp)]
        eng.run()
        for p, r, s in zip(prompts, reqs, (sp, long, sp)):
            assert r.finish_reason == "length"
            assert r.output_tokens == _naive(models, "llama", p, s)
        assert not eng._unread() and not eng.cache.tables
        eng.close()

    @pytest.mark.parametrize("name", ["llama", "state"])
    def test_finish_by_eos_is_found_a_step_late_and_leaves_nothing(
            self, models, name):
        """The step dispatched past an ``eos_token_id`` leaves no token in
        the stream, and neither K/V nor state for the slot's next tenant
        (one slot: every request takes the place of the one before)."""
        sp = _greedy(12)
        first = _prompt(6)
        free = _naive(models, name, first, sp)
        eos = free[4]
        want = _naive(models, name, first, sp, eos=eos)
        assert want[-1] == eos and len(want) < len(free)
        eng = _engine(models(name), max_slots=1, eos_token_id=eos)
        seen = []
        a = eng.add_request(first, sp, on_token=lambda r, t: seen.append(t))
        others = [_prompt(n, seed=1) for n in (9, 4)]
        reqs = [eng.add_request(p, sp) for p in others]
        steps0 = eng._m.decode_step.count
        eng.run()
        assert a.output_tokens == want == seen and a.finish_reason == "stop"
        for p, r in zip(others, reqs):
            assert r.output_tokens == _naive(models, name, p, sp, eos=eos)
        # the late step ran: one decode step more than the stream's tokens
        n_tokens = sum(len(r.output_tokens) - 1 for r in [a] + reqs)
        late = sum(r.finish_reason == "stop" for r in [a] + reqs)
        assert eng._m.decode_step.count - steps0 == n_tokens + late
        assert eng.stats()["perf"]["decode_step"]["drains"] == {}
        assert not eng.cache.tables and not eng._unread()
        eng.close()

    def test_a_seeded_sampling_row_beside_a_greedy_one(self, models):
        eng = _engine(models("llama"))
        sps = [SamplingParams(max_new_tokens=10, temperature=0.8, top_k=7,
                              top_p=0.9, seed=123),
               _greedy(10),
               SamplingParams(max_new_tokens=8, temperature=1.2, seed=5)]
        prompts = [_prompt(n, seed=2) for n in (6, 9, 12)]
        outs = eng.generate(prompts, sps)
        for p, s, out in zip(prompts, sps, outs):
            assert out == _naive(models, "llama", p, s)
        assert outs[0] != _naive(models, "llama", prompts[0], _greedy(10))
        eng.close()

    @pytest.mark.parametrize("name", ["llama", "state"])
    def test_a_pool_small_enough_to_preempt(self, models, name):
        """A victim's token in flight is read before it is re-queued: its
        re-prefill holds it."""
        eng = _engine(models(name), block_size=4, max_slots=3,
                      max_model_len=32, num_blocks=10)
        sp = _greedy(14)
        prompts = [_prompt(n, seed=3 + i) for i, n in enumerate((7, 6, 7, 6))]
        outs = eng.generate(prompts, sp)
        stats = eng.stats()
        assert stats["num_preemptions"] >= 1 and stats["num_failed"] == 0
        assert stats["perf"]["decode_step"]["drains"]["preemption"] >= 1
        for p, out in zip(prompts, outs):
            assert out == _naive(models, name, p, sp)
        eng.close()

    def test_prefix_cache_on(self, models):
        """Blocks a decode step fills are indexed once the token that
        completes them has been read: a later prompt that continues an
        earlier stream hits them."""
        eng = _engine(models("llama"), max_slots=2)
        sp = _greedy(12)
        shared = _prompt(16, seed=4)
        first = shared + _prompt(3, seed=5)
        (out,) = eng.generate([first], sp)
        assert out == _naive(models, "llama", first, sp)
        # 19 + 12 tokens: blocks 0..2 are full, the third filled by decode
        follow = (first + out)[:24] + _prompt(2, seed=6)
        sibling = shared + _prompt(5, seed=7)
        outs = eng.generate([follow, sibling], sp)
        assert outs[0] == _naive(models, "llama", follow, sp)
        assert outs[1] == _naive(models, "llama", sibling, sp)
        pc = eng.stats()["prefix_cache"]
        assert pc["hits"] == 2 and pc["blocks_saved"] == 3 + 2
        assert eng.stats()["perf"]["decode_step"]["drains"] == {}
        eng.close()

    @pytest.mark.parametrize("how", ["cancel", "deadline"])
    def test_cancel_and_deadline_during_a_step_in_flight(self, models, how):
        eng = _engine(models("llama"))
        sp = _greedy(12)
        prompts = [_prompt(n, seed=8) for n in (5, 9)]
        a, b = (eng.add_request(p, sp) for p in prompts)
        _steps(eng, 3)
        _in_flight(eng)
        assert a.in_flight == 1
        n_before = len(a.output_tokens)
        if how == "cancel":
            assert eng.cancel(a.rid)
        else:
            a.deadline = time.monotonic() - 1.0
            eng.step()
        assert a.state is RequestState.CANCELLED and a.in_flight == 0
        if how == "deadline":
            assert isinstance(a.error, DeadlineExceeded)
        # the token that was in flight reached the stream before the end
        assert len(a.output_tokens) == n_before + 1
        want = _naive(models, "llama", prompts[0], sp)
        assert a.output_tokens == want[:len(a.output_tokens)]
        eng.run()
        assert b.output_tokens == _naive(models, "llama", prompts[1], sp)
        assert eng.stats()["perf"]["decode_step"]["drains"] == {how: 1}
        assert not eng.cache.tables
        eng.close()


# ---------------------------------------------------------------------------
# (b) structure: the next step goes out before this one's tokens are read
# ---------------------------------------------------------------------------

class TestTheNextStepIsDispatchedFirst:
    def test_decode_of_step_n_plus_1_begins_before_the_wait_of_n_ends(
            self, models):
        eng = _engine(models("llama"), max_slots=2)
        eng.generate([_prompt(4)], _greedy(3))              # compiled
        tr = telemetry.tracer()
        tr.clear()
        eng.generate([_prompt(5), _prompt(7)], _greedy(14))
        decodes = sorted(tr.find("engine.decode"), key=lambda s: s.t0)
        waits = sorted(tr.find("engine.decode_wait"), key=lambda s: s.t0)
        assert len(decodes) == len(waits) == 13
        for n in range(len(decodes) - 1):
            # step n+1 is on its way to the device ...
            assert decodes[n + 1].t1 <= waits[n].t0
            # ... and nothing is read before its own dispatch
            assert decodes[n].t1 <= waits[n].t0
        assert waits[-1].t0 >= decodes[-1].t1
        eng.close()

    def test_pipelined_step_share_over_64_steps_with_admissions(self, models):
        """A closed loop of four callers on four slots: every completion
        admits the next request, and no admission drains."""
        eng = _engine(models("llama"), max_slots=4)
        rng = np.random.RandomState(0)
        todo = [(_prompt(int(n), seed=20 + i), _greedy(int(m)))
                for i, (n, m) in enumerate(zip(rng.choice([4, 9], 24),
                                               rng.choice([8, 13], 24)))]
        reqs = []

        def refill(req=None, tok=None):
            live = sum(not r.state.is_terminal for r in reqs)
            while todo and live < 4:
                p, sp = todo.pop()
                reqs.append(eng.add_request(p, sp))
                reqs[-1].want = (p, sp)
                live += 1

        steps0 = eng._m.decode_step.count
        refill()
        while eng.step() or todo:
            refill()
        assert eng._m.decode_step.count - steps0 >= 64
        step = eng.stats()["perf"]["decode_step"]
        assert step["pipelined_step_share"] > 0.9
        assert step["drains"] == {}
        for r in reqs:
            assert r.output_tokens == _naive(models, "llama", *r.want)
        eng.close()

    @pytest.mark.parametrize("reason", ["preemption", "cancel", "deadline",
                                        "fault", "close", "kv_export"])
    def test_each_drain_reason_is_counted_once_when_planted(
            self, models, reason):
        tight = reason == "preemption"
        eng = _engine(models("llama"), block_size=4, max_slots=2,
                      max_model_len=32, num_blocks=9 if tight else None)
        sp = _greedy(12)
        a = eng.add_request(_prompt(7, seed=10), sp)
        b = eng.add_request(_prompt(6, seed=10), sp)
        _steps(eng, 2)
        _in_flight(eng)
        if reason == "preemption":
            eng.run()
            assert eng.stats()["num_preemptions"] == 1
        elif reason == "cancel":
            eng.cancel(b.rid)
        elif reason == "deadline":
            b.deadline = time.monotonic() - 1.0
            eng.step()
        elif reason == "fault":
            with FaultPlan.parse("serving.decode:error@1"):
                eng.step()
            assert a.state is b.state is RequestState.FAILED
        elif reason == "close":
            eng.close()
            assert a.state is b.state is RequestState.CANCELLED
        elif reason == "kv_export":
            eng.export_kv_frames([])
        assert b.in_flight == 0
        if reason != "deadline":        # there the loop went on with ``a``
            assert a.in_flight == 0 and not eng._unread()
        assert eng.stats()["perf"]["decode_step"]["drains"] == {reason: 1}
        # whatever was read before the end is the serial stream's head
        for r in (a, b):
            want = _naive(models, "llama", r.prompt, sp)
            assert r.output_tokens == want[:len(r.output_tokens)]
        eng.close()


# ---------------------------------------------------------------------------
# (c) a step that fails on the device
# ---------------------------------------------------------------------------

class TestAStepThatFailsOnTheDevice:
    def test_fails_the_rows_in_flight_and_the_engine_serves_on(self, models):
        eng = _engine(models("llama"), max_slots=2)
        sp = _greedy(10)
        a = eng.add_request(_prompt(5, seed=11), sp)
        b = eng.add_request(_prompt(8, seed=11), sp)
        waiting = eng.add_request(_prompt(6, seed=11), sp)
        _steps(eng, 3)
        _in_flight(eng)
        got = [len(a.output_tokens), len(b.output_tokens)]
        real, raised = eng._fetch, []

        def broken(x):
            if np.ndim(x) == 1 and not raised:      # a decode step's tokens
                raised.append(True)
                raise RuntimeError("device step failed")
            return real(x)

        eng._fetch = broken
        failed0 = eng.stats()["num_failed"]
        eng.step()          # dispatches a step behind the one that failed
        assert raised and not eng._unread()
        for r, n in zip((a, b), got):
            assert r.state is RequestState.FAILED
            assert "device step failed" in str(r.error)
            assert len(r.output_tokens) == n and r.in_flight == 0
        assert eng.stats()["num_failed"] - failed0 == 2
        assert waiting.state is RequestState.WAITING
        later = eng.add_request(_prompt(7, seed=12), sp)
        eng.run()
        for r in (waiting, later):
            assert r.state is RequestState.FINISHED
            assert r.output_tokens == _naive(models, "llama", r.prompt, sp)
        assert not eng.cache.tables
        eng.close()

    def test_a_prefill_that_fails_on_the_device_fails_its_one_request(
            self, models):
        eng = _engine(models("llama"), max_slots=2)
        sp = _greedy(8)
        a = eng.add_request(_prompt(5, seed=13), sp)
        _steps(eng, 3)
        real, raised = eng._fetch, []

        def broken(x):
            if np.ndim(x) == 0 and not raised:      # a prefill's token
                raised.append(True)
                raise RuntimeError("prefill failed")
            return real(x)

        eng._fetch = broken
        b = eng.add_request(_prompt(9, seed=13), sp)
        eng.run()
        assert raised and b.state is RequestState.FAILED and not b.output_tokens
        assert a.output_tokens == _naive(models, "llama", a.prompt, sp)
        c = eng.add_request(_prompt(9, seed=13), sp)
        eng.run()
        assert c.output_tokens == _naive(models, "llama", c.prompt, sp)
        eng.close()
