"""The sparse experts' grouped product's share of its roofline, in %: as
``roofline`` reads a kernel's (least time over the kernel's summed device
time in the trace), with the work of a kernel whose bytes depend on where
the router sent the rows: an expert's weights are read once for all the rows
of a step that chose it, so the bytes follow the *distinct* experts of each
step and layer, which only the program can count.

``work`` names the count of the configuration's architecture
(``moe_experts(cfg, pairs, experts_read)``). The (token, expert) pairs are
the traffic's: every output token a decode step made in the window and every
prompt token prefilled in it, times ``num_experts_per_tok`` and the sparse
layers. The expert weight sets read are the program's: its counter of the
share of the held experts that a decode step's rows chose (``decode_share``,
a path into ``engine.stats()``: the mean over its latest steps and sparse
layers) times the experts held, the sparse layers and the decode programs in
the trace (``decode_program``), and the same for the prefills
(``prefill_share``, over the prompts whose first token came in the window).
Never the expectation under uniform routing: real routing is less even, and
a share taken of more bytes than were needed can pass 100 %.

A program without the counters or the kernel, and a run without a chip's
peaks, have nothing to read."""
import sys

from benchmark.lib import trace as T, work as W
from benchmark.readers import stat


def read(facts, kernel, work, decode_program, decode_share, prefill_share):
    tr, peak, cfg = facts["trace"], facts["peaks"], facts["cfg"]
    arch = facts["arch"]
    count = getattr(arch, work, None)
    if tr is None or peak is None or count is None:
        return None
    kernel_s = T.total_s(T.matching(tr.ops, kernel))
    dec = stat.read(facts, decode_share)
    pre = stat.read(facts, prefill_share)
    if kernel_s <= 0 or dec is None:
        return None
    layers, held = arch.sparse_layers(cfg), cfg["num_experts"]
    steps = len(T.durations_ms(T.matching(tr.modules, decode_program)))
    prompts = facts["prefill_lens"]
    pairs = (len(facts["decode_contexts"]) + sum(prompts)) * (
        cfg["num_experts_per_tok"] * layers)
    experts_read = (dec * steps + (pre or 0.0) * len(prompts)) * held * layers
    need = count(cfg, pairs, experts_read)
    if need["flops"] <= 0 and need["bytes"] <= 0:
        return None
    least_s, bound = W.roofline_seconds(need, peak)
    least_s /= facts["chips"]
    print(f"[bench] roofline {kernel}: {steps} decode steps at {dec:.3f} of "
          f"{held} experts a layer, {len(prompts)} prefills at "
          f"{pre if pre is None else round(pre, 3)}; least "
          f"{least_s * 1e3:.3f} ms by {bound}, kernel "
          f"{kernel_s * 1e3:.3f} ms", file=sys.stderr)
    return 100.0 * least_s / kernel_s
