"""The whole step's share of the chip's peak bf16 FLOP/s, in %: the FLOPs
the mathematics needs for the tokens the window processed (work functions of
the benchmark, nothing recomputed or padded), over the window's seconds, the
chips and the peak. ``mode`` "train": tokens of finished steps x forward and
backward FLOPs per token. ``mode`` "serve": every prompt whose first token
came in the window and every output token made by a decode step in it."""
from benchmark.lib import work


def read(facts, mode):
    cfg, peak = facts["cfg"], facts["peaks"]
    window_s = facts.get("window_s") or 0.0
    if window_s <= 0 or peak is None:
        return None
    if mode == "train":
        flops = facts["train_tokens"] * work.train_flops_per_token(
            cfg, facts["traffic"]["seq"])
    elif mode == "serve":
        flops = sum(work.prefill_flops(cfg, n) for n in facts["prefill_lens"])
        flops += sum(work.decode_flops(cfg, c) for c in facts["decode_contexts"])
    else:
        raise ValueError(f"unknown mfu mode {mode!r}")
    if flops <= 0:
        return None
    return 100.0 * flops / window_s / facts["chips"] / peak["bf16_flops_per_s"]
