"""The trace reduction against a small trace worked out by hand, and against
a slice recorded on the chip."""
import json
import os

import pytest

from benchmark.lib import trace as T

D = "/device:TPU:0"

# one prefill and two decode steps; times in ns
HAND = {
    "modules": {D: [["jit_prefill(11)", 1000, 400], ["jit_decode(22)", 2000, 1000],
                    ["jit_decode(22)", 3500, 1000],
                    ["jit_decode(22)", 5000, 1000]]},      # outside the window
    "ops": {D: [
        ["fusion.1", 1000, 100], ["fusion.2", 1150, 200],
        ["fusion.7", 1300, 100],            # overlaps fusion.2: union 1150..1400
        ["copy.3", 2000, 100], ["paged_attention.4", 2100, 300],
        ["paged_attention.5", 2400, 300], ["fusion.6", 2750, 250],
        ["copy.3", 3500, 100], ["paged_attention.4", 3600, 300],
        ["paged_attention.5", 3900, 300], ["fusion.6", 4250, 250],
        ["copy.3", 5000, 100]]},
    "host": [["engine.prefill", 900, 200], ["engine.decode", 1500, 450],
             ["engine.decode", 3050, 400]],
    "window": [800, 4700],
}


@pytest.fixture
def hand():
    return T.Trace.from_json(HAND)


def test_busy_idle_and_window(hand):
    assert hand.window_s == pytest.approx(3900e-9)
    # prefill 100 + 250 (union), each decode 100 + 300 + 300 + 250
    assert T.busy_s(hand) == pytest.approx((350 + 950 + 950) * 1e-9)
    from benchmark.readers import idle_share
    assert idle_share.read({"trace": hand}) == pytest.approx(
        100 * (1 - 2250 / 3900))


def test_programs_and_kernels_by_name(hand):
    dec = T.matching(hand.modules, r"^jit_decode")
    assert T.durations_ms(dec) == [1e-3, 1e-3]        # the third is outside
    assert T.total_s(T.matching(hand.modules, r"^jit_prefill")) == \
        pytest.approx(400e-9)
    assert T.total_s(T.matching(hand.ops, "paged_attention")) == \
        pytest.approx(1200e-9)
    from benchmark.readers import program_time, program_time_per, time_share
    assert program_time.read({"trace": hand}, r"^jit_decode") == 1e-3
    # 400 ns of prefill over 0.5 thousand prompt tokens
    assert program_time_per.read(
        {"trace": hand, "prefill_lens": [200, 300]}, r"^jit_prefill",
        "prefill_lens", 1000.0) == pytest.approx(400e-6 / 0.5)
    assert time_share.read({"trace": hand}, "paged_attention") == \
        pytest.approx(100 * 1200 / 2250)
    assert program_time.read({"trace": hand}, r"^jit_train_step") is None
    assert time_share.read({"trace": hand}, "flash_attention") is None


def test_gaps_are_labelled_by_the_covering_host_span(hand):
    gaps = T.gaps(hand)
    assert [(label, dur) for label, _, dur in gaps] == \
        [("engine.decode", 600), ("engine.decode", 500)]
    assert T.top_gaps(hand) == [["engine.decode", pytest.approx(1100e-9)]]
    from benchmark.readers import gap_stat
    assert gap_stat.read({"trace": hand}, 50) == pytest.approx(550e-6)
    assert gap_stat.read({"trace": hand}, 50, under_span="engine.prefill") is None


def test_breakdown_names_program_and_kernel(hand):
    top = T.top_ops(hand)
    assert [t[0] for t in top] == ["jit_decode/paged_attention",
                                   "jit_decode/fusion", "jit_prefill/fusion",
                                   "jit_decode/copy"]
    assert [round(t[1] * 1e9) for t in top] == [1200, 500, 400, 200]


def test_roofline_and_mfu_from_the_benchmarks_own_work(hand):
    from benchmark.arch import llama
    from benchmark.lib import peaks
    from benchmark.readers import mfu, roofline

    with open(os.path.join(os.path.dirname(__file__), "..", "..", "benchmark",
                           "configs", "mistral-7b-v0.3-serve-l8.json")) as f:
        cfg = json.load(f)
    peak = peaks.peaks("TPU v5 lite")
    facts = {"trace": hand, "cfg": cfg, "arch": llama, "peaks": peak, "chips": 1,
             "decode_contexts": [300] * 64, "prefill_lens": [200],
             "window_s": 2.0}
    need = llama.paged_attention_decode(cfg, [300] * 64)
    want = 100 * (need["bytes"] / 819e9) / 1200e-9
    assert roofline.read(facts, "paged_attention", "paged_attention_decode") \
        == pytest.approx(want)
    flops = llama.prefill_flops(cfg, 200) + 64 * llama.decode_flops(cfg, 300)
    assert mfu.read(facts, "serve") == pytest.approx(
        100 * flops / 2.0 / 197e12)
    # nothing to read is nothing reported, never 0
    assert roofline.read(dict(facts, decode_contexts=[]), "paged_attention",
                         "paged_attention_decode") is None
    assert mfu.read(dict(facts, peaks=None), "serve") is None


def test_op_and_base_names():
    text = ("%paged_attention.8 = bf16[32,8,4,128]{3,2,1,0:T(4,128)(2,1)} "
            "custom-call(s32[32,128]{1,0:T(8,128)} %bt.1), "
            "custom_call_target=\"tpu_custom_call\"")
    assert T.op_name(text) == "paged_attention.8"
    assert T.base_name("paged_attention.8") == "paged_attention"
    assert T.base_name("jit_decode(2631307917366441555)") == "jit_decode"
    assert T.base_name("fusion") == "fusion"


def test_union_clips_and_merges():
    evs = [T.Ev("a", 0, 10), T.Ev("b", 5, 10), T.Ev("c", 30, 5)]
    assert T.union_ns(evs) == 20
    assert T.union_ns(evs, lo=8, hi=32) == 7 + 2


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(os.path.dirname(__file__), "recorded_trace_serve.json")
    with open(path) as f:
        doc = json.load(f)
    return T.Trace.from_json(doc), doc["recorded"]


def test_recorded_slice_of_the_chip(recorded):
    """0.21 s of the decode cell as the profiler wrote it on the v5e. The
    expected numbers were worked out when the slice was cut, by other
    arithmetic (a timeline of 100 ns cells for the union)."""
    tr, want = recorded
    dev = tr.devices[0]
    assert len(tr.ops[dev]) == want["n_ops"]
    busy_ns = T.busy_s(tr) * 1e9
    # each operation rounds up to a cell: at most 100 ns an operation over
    assert 0 <= want["busy_ns_timeline_100ns"] - busy_ns <= 100 * want["n_ops"]
    paged = T.matching(tr.ops, "paged_attention")
    assert sum(e.dur for e in paged[dev]) == want["paged_attention_ns"]
    assert len(paged[dev]) == want["paged_calls"]
    assert [e.dur for e in T.matching(tr.modules, r"^jit_decode")[dev]] == \
        want["decode_ns"]
    assert [e.dur for e in T.matching(tr.modules, r"^jit_prefill")[dev]] == \
        want["prefill_ns"]
    # the kernel is the largest operation of the decode program
    assert T.top_ops(tr)[0][0] == "jit_decode/paged_attention"
    # the device waits between decode steps while the host is in engine.decode
    labels = {label for label, _, _ in T.gaps(tr, min_ns=1_000_000)}
    assert "engine.decode" in labels
    from benchmark.readers import idle_share, program_time
    assert 0 < idle_share.read({"trace": tr}) < 100
    assert program_time.read({"trace": tr}, r"^jit_decode") == \
        pytest.approx(sum(want["decode_ns"]) / 2 / 1e6)


def test_stat_reader_digs_a_dotted_path():
    from benchmark.readers import stat

    facts = {"stats": {"prefix_cache": {"hits": 3, "lookups": 12},
                       "num_finished": 7}}
    assert stat.read(facts, "num_finished") == 7.0
    assert stat.read(facts, "prefix_cache.hits", per="prefix_cache.lookups",
                     scale=100.0) == 25.0
    assert stat.read(facts, "prefix_cache.misses") is None
    assert stat.read({}, "num_finished") is None
