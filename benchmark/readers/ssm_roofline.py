"""A state-space kernel's share of its roofline, in %: as ``roofline`` reads
a kernel's (the least time the chip could take for the kernel's work in the
traced window over the kernel's summed device time in the trace), for the
two kinds of work a recurrent layer has, which the generic reader does not
know by name:

- ``ssm_state_update_decode``: one step of the recurrence for every output
  token a decode step made in the window (``decode_contexts``, one entry a
  token): each token's state read once and written once a layer. Rows of
  slots that hold no request are stepped by the kernel too and are nobody's
  work.
- ``ssd_prefill``: the recurrence over the prompts whose first token came
  in the window (``prefill_lens``), unpadded.

``work`` names the count of the configuration's architecture; the larger
of its two bounds is taken and logged. An architecture without the count, a
trace without the kernel (a parent that lacks it) and a run without a
chip's peaks have nothing to read."""
import sys

from benchmark.lib import trace as T, work as W

# what of the window each count is given
WORK = {"ssm_state_update_decode": lambda f: len(f["decode_contexts"]),
        "ssd_prefill": lambda f: f["prefill_lens"]}


def read(facts, kernel, work):
    tr, peak = facts["trace"], facts["peaks"]
    count = getattr(facts["arch"], work, None)
    if tr is None or peak is None or count is None:
        return None
    kernel_s = T.total_s(T.matching(tr.ops, kernel))
    need = count(facts["cfg"], WORK[work](facts))
    if kernel_s <= 0 or (need["flops"] <= 0 and need["bytes"] <= 0):
        return None
    least_s, bound = W.roofline_seconds(need, peak)
    least_s /= facts["chips"]
    print(f"[bench] roofline {kernel}: least {least_s * 1e3:.3f} ms by "
          f"{bound}, kernel {kernel_s * 1e3:.3f} ms", file=sys.stderr)
    return 100.0 * least_s / kernel_s
