"""A kernel's share of its roofline, in %: the least time the chip could take
for the kernel's work in the traced window (the larger of FLOPs over peak
FLOP/s and bytes over peak bytes/s, both from the traffic and the shapes, not
from what the kernel does) over the kernel's summed device time in the trace.
``work`` names how the work is counted; the reader logs which bound it is."""
import sys

from benchmark.lib import trace as T, work as W


def _paged_attention_decode(facts):
    return W.paged_attention_decode(facts["cfg"], facts["decode_contexts"])


def _flash_attention_train(facts):
    job = facts["traffic"]
    steps = facts["train_tokens"] / (job["batch"] * job["seq"])
    w = W.flash_attention_train(facts["cfg"], job["batch"], job["seq"])
    return {k: v * steps for k, v in w.items()}


WORK = {"paged_attention_decode": _paged_attention_decode,
        "flash_attention_train": _flash_attention_train}


def read(facts, kernel, work):
    tr, peak = facts["trace"], facts["peaks"]
    if tr is None or peak is None:
        return None
    kernel_s = T.total_s(T.matching(tr.ops, kernel))
    need = WORK[work](facts)
    if kernel_s <= 0 or (need["flops"] <= 0 and need["bytes"] <= 0):
        return None
    least_s, bound = W.roofline_seconds(need, peak)
    least_s /= facts["chips"]
    print(f"[bench] roofline {kernel}: least {least_s * 1e3:.3f} ms by "
          f"{bound}, kernel {kernel_s * 1e3:.3f} ms", file=sys.stderr)
    return 100.0 * least_s / kernel_s
