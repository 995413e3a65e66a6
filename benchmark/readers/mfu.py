"""The whole step's share of the chip's peak bf16 FLOP/s, in %: the FLOPs
the mathematics needs for the tokens the window processed (the work counts of
the configuration's architecture, nothing recomputed or padded), over the
window's seconds, the chips and the peak. ``mode`` "train": tokens of
finished steps x forward and backward FLOPs per token. ``mode`` "serve":
every prompt whose first token came in the window and every output token made
by a decode step in it. An architecture that does not give the counts a mode
needs has nothing to read."""

NEEDS = {"train": ("train_flops_per_token",),
         "serve": ("prefill_flops", "decode_flops")}


def read(facts, mode):
    cfg, peak = facts["cfg"], facts["peaks"]
    window_s = facts.get("window_s") or 0.0
    counts = [getattr(facts["arch"], n, None) for n in NEEDS[mode]]
    if window_s <= 0 or peak is None or None in counts:
        return None
    if mode == "train":
        (per_token,) = counts
        flops = facts["train_tokens"] * per_token(cfg, facts["traffic"]["seq"])
    else:
        prefill, decode = counts
        flops = sum(prefill(cfg, n) for n in facts["prefill_lens"])
        flops += sum(decode(cfg, c) for c in facts["decode_contexts"])
    if flops <= 0:
        return None
    return 100.0 * flops / window_s / facts["chips"] / peak["bf16_flops_per_s"]
