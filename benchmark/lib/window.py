"""Window accounting: from stamped requests to the serving cell's numbers.

Everything is taken over all the work and all the time of the window
``[t_open, t_close)``: tokens that reached the client in it, gaps that end in
it, first tokens that came in it. Requests that the close cut off are
cancelled, not failed. A failed or refused request counts as the worst first
token: the window's whole length.
"""
from __future__ import annotations


def percentile(values, q):
    """Linear-interpolated percentile (numpy's default rule)."""
    if not values:
        return None
    v = sorted(values)
    pos = (len(v) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def account(records, t_open, t_close):
    """Counts and samples of one window over the load generator's records."""
    window_s = t_close - t_open

    def inside(t):
        return t is not None and t_open <= t < t_close

    tokens = 0
    ttft_ms, itl_ms, lag_ms = [], [], []
    attempted = failed = finished = cancelled = 0
    decode_contexts, prefill_lens = [], []
    for r in records:
        if r.t_send is None or r.t_send >= t_close:
            continue
        if r.t_end is not None and r.t_end < t_open:
            continue
        attempted += 1
        if inside(r.t_send):
            lag_ms.append((r.t_send - r.t_due) * 1e3)
        if r.status == "failed":
            failed += 1
            if not r.t_tokens:
                ttft_ms.append(window_s * 1e3)
        elif r.status == "ok" and inside(r.t_end):
            finished += 1
        elif r.status == "cancelled":
            cancelled += 1
        n_prompt = len(r.prompt)
        for i, t in enumerate(r.t_tokens):
            if not inside(t):
                continue
            tokens += 1
            if i == 0:
                ttft_ms.append((t - r.t_due) * 1e3)
                prefill_lens.append(n_prompt)
            else:
                itl_ms.append((t - r.t_tokens[i - 1]) * 1e3)
                # token i is made by a decode step whose query sees the
                # prompt, the i tokens before it and itself
                decode_contexts.append(n_prompt + i)
    return {
        "window_s": window_s, "tokens": tokens, "attempted": attempted,
        "failed": failed, "finished": finished, "cancelled": cancelled,
        "ttft_ms": ttft_ms, "itl_ms": itl_ms, "lag_ms": lag_ms,
        "decode_contexts": decode_contexts, "prefill_lens": prefill_lens,
    }
