"""A kernel's share of its roofline, in %: the least time the chip could take
for the kernel's work in the traced window (the larger of FLOPs over peak
FLOP/s and bytes over peak bytes/s, both from the traffic and the shapes, not
from what the kernel does) over the kernel's summed device time in the trace.
``work`` names the count of the configuration's architecture that says how
much work that is (an architecture that does not give it has nothing to
read); the reader logs which bound it is."""
import sys

from benchmark.lib import trace as T, work as W


def _paged_attention_decode(count, facts):
    return count(facts["cfg"], facts["decode_contexts"])


def _flash_attention_train(count, facts):
    job = facts["traffic"]
    steps = facts["train_tokens"] / (job["batch"] * job["seq"])
    w = count(facts["cfg"], job["batch"], job["seq"])
    return {k: v * steps for k, v in w.items()}


# what of the window each count is given
WORK = {"paged_attention_decode": _paged_attention_decode,
        "flash_attention_train": _flash_attention_train}


def read(facts, kernel, work):
    tr, peak = facts["trace"], facts["peaks"]
    count = getattr(facts["arch"], work, None)
    if tr is None or peak is None or count is None:
        return None
    kernel_s = T.total_s(T.matching(tr.ops, kernel))
    need = WORK[work](count, facts)
    if kernel_s <= 0 or (need["flops"] <= 0 and need["bytes"] <= 0):
        return None
    least_s, bound = W.roofline_seconds(need, peak)
    least_s /= facts["chips"]
    print(f"[bench] roofline {kernel}: least {least_s * 1e3:.3f} ms by "
          f"{bound}, kernel {kernel_s * 1e3:.3f} ms", file=sys.stderr)
    return 100.0 * least_s / kernel_s
