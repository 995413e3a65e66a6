"""The Llama architecture's work counts (``arch/llama.py``), what every
architecture's share (``lib/work.py``) and the peaks against hand-worked
shapes."""
import json
import os

import pytest

from benchmark.arch import llama
from benchmark.lib import peaks, work

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _cfg(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


SERVE = _cfg("mistral-7b-v0.3-serve-l8")
TRAIN = _cfg("mistral-7b-v0.3-train-l2")

# q 4096x4096 + k,v 2x4096x1024 + o 4096x4096 + gate,up,down 3x4096x14336
LAYER = 16777216 + 8388608 + 16777216 + 176160768


@pytest.mark.parametrize("what,got,want", [
    ("layer matmul params", llama.layer_matmul_params(SERVE), 218103808),
    ("layer sum", LAYER, 218103808),
    ("head params", llama.head_params(SERVE), 4096 * 32768),
    # 8 x (layer + two norms) + embedding + final norm + head
    ("serve params", llama.num_params(SERVE),
     8 * (218103808 + 8192) + 134217728 + 4096 + 134217728),
    ("serve params, the issue's 2.01 B", round(llama.num_params(SERVE) / 1e7), 201),
    ("train params, the issue's 704.6 M", llama.num_params(TRAIN), 704663552),
    # K and V: 2 x 8 heads x 128 x 2 bytes x 8 layers
    ("kv bytes per token", llama.kv_bytes_per_token(SERVE), 32768),
    ("causal pairs", work.causal_pairs(4), 10),
    # one layer, 3 queries seeing 1, 2 and 3 keys: 4 x 32 x 128 x 6
    ("attention flops", llama.attention_flops(SERVE, 6), 4 * 4096 * 6),
    # 2 x (8 layers + head) matmuls + 8 layers x 4 x 4096 x 300 context
    ("decode flops", llama.decode_flops(SERVE, 300),
     2 * (8 * 218103808 + 134217728) + 8 * 16384 * 300),
    # every layer for 512 tokens, causal attention, the head once
    ("prefill flops", llama.prefill_flops(SERVE, 512),
     2 * 8 * 218103808 * 512 + 8 * 16384 * (512 * 513 // 2) + 2 * 134217728),
    # 3 x (2 x (2 layers + head) + 2 layers x 16384 x 4097 / 2)
    ("train flops per token", llama.train_flops_per_token(TRAIN, 4096),
     3 * (2 * (2 * 218103808 + 134217728) + 2 * 16384 * 4097 / 2)),
])
def test_counts(what, got, want):
    assert got == want, what


def test_paged_attention_work_counts_live_context_only():
    # two decode tokens seeing 100 and 300 positions
    w = llama.paged_attention_decode(SERVE, [100, 300])
    assert w["bytes"] == 400 * 32768 + 2 * 2 * 32 * 128 * 2 * 8
    assert w["flops"] == 8 * 16384 * 400
    t, bound = work.roofline_seconds(w, peaks.peaks("TPU v5 lite"))
    assert bound == "bytes" and t == pytest.approx(w["bytes"] / 819e9)


def test_flash_attention_work():
    w = llama.flash_attention_train(TRAIN, batch=2, seq=4096)
    one_matmul = 2 * 32 * 128 * (4096 * 4097 // 2)      # causal half, 1 row
    assert w["flops"] == 2 * 2 * 7 * one_matmul           # rows x layers x 7
    q = 2 * 4096 * 32 * 128
    kv = 2 * 4096 * 8 * 128
    assert w["bytes"] == 2 * (6 * q + 6 * kv) * 2
    _, bound = work.roofline_seconds(w, peaks.peaks("TPU v5 lite"))
    assert bound == "flops"


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no peaks known"):
        peaks.peaks("cpu")
    p = peaks.peaks("TPU v5 lite")
    assert (p["bf16_flops_per_s"], p["hbm_bytes_per_s"]) == (197e12, 819e9)
