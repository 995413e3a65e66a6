"""Serve driver: Gateway -> FleetRouter -> LocalReplica -> LLMEngine under a
traffic mix, as ``chip_smoke.serve_leg`` builds the stack, measured from the
client's side of ``POST /v1/completions`` with ``"stream": true``. Which
model the engine serves is the configuration's architecture's to say
(``ctx.arch``: ``build_model``, ``shapes``).

Set-up: weights from the seed (the benchmark's, put into the program's
model), the cell's prefill buckets and the decode step warmed through the
gateway, the load started; then the window. After the window: peak memory
read, the served stack stopped and freed, and a sample of the finished
requests compared with the plain reference (``correct``).
"""
from __future__ import annotations

import contextlib
import gc
import http.client
import json
import time

import numpy as np

from benchmark.lib import harness, loadgen, window
from benchmark.lib import weights as weights_mod
from benchmark.drivers_common import Tracing


@contextlib.contextmanager
def placeholder_parameters():
    """Build the program's Layers without materialising its own initial
    weights (8 GB in float32 here, which would set the process's peak
    memory): every parameter is a shape until the benchmark's weights take
    its place."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.core.dtype import convert_dtype
    from paddle_tpu.core.tensor import Parameter
    from paddle_tpu.nn import layer as layer_mod

    orig = layer_mod.Layer.create_parameter

    def create(self, shape, attr=None, dtype=None, is_bias=False,
               default_initializer=None):
        dt = convert_dtype(dtype) if dtype is not None else self._dtype
        p = Parameter(jnp.zeros((), dt))
        p._value = jax.ShapeDtypeStruct(tuple(int(s) for s in shape), dt)
        return p

    layer_mod.Layer.create_parameter = create
    try:
        yield
    finally:
        layer_mod.Layer.create_parameter = orig


def build_model(ctx):
    """The program's model holding the benchmark's weights, in the dtype the
    configuration serves in."""
    import jax

    cfg = ctx.cfg
    with placeholder_parameters():
        model = ctx.arch.build_model(cfg, cfg["max_position_embeddings"])
    w = weights_mod.make_weights(ctx.arch.shapes(cfg), ctx.seed, cfg["dtype"])
    for name, p in model.named_parameters():
        if name not in w or tuple(p._value.shape) != w[name].shape:
            raise ValueError(f"the model's {name} {tuple(p._value.shape)} has "
                             f"no weight of the benchmark's of that shape")
        p._value = w.pop(name)
    if w:
        raise ValueError(f"weights the model has no place for: {sorted(w)}")
    model.to(dtype=cfg["dtype"])       # the user's path: buffers follow
    jax.block_until_ready([p._value for p in model.parameters()])
    return model


def _post(host, port, prompt, max_tokens):
    conn = http.client.HTTPConnection(host, port, timeout=1200)
    try:
        conn.request("POST", "/v1/completions", json.dumps(
            {"prompt": prompt, "max_tokens": max_tokens, "temperature": 0.0}),
            {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


def warm_up(ctx, gateway, vocab):
    """One request per prefill bucket the mix uses, a few tokens each, so the
    bucket's program and the decode step are compiled (or loaded)."""
    rng = np.random.RandomState([ctx.seed & 0xFFFFFFFF, ctx.seed >> 32, 11])
    for n in ctx.traffic["warm_prompt_lens"]:
        t = time.monotonic()
        status, body = _post(gateway.host, gateway.port,
                             rng.randint(1, vocab, int(n)).tolist(), 3)
        if status != 200 or len(body["choices"][0]["token_ids"]) != 3:
            raise RuntimeError(f"warm-up of prompt length {n}: HTTP {status} "
                               f"{str(body)[:300]}")
        ctx.log(f"warmed prompt length {n} in {time.monotonic() - t:.1f} s")


def run(ctx):
    from paddle_tpu.serving import (FleetRouter, Gateway, LLMEngine,
                                    LocalReplica)

    cfg, mix = ctx.cfg, ctx.traffic
    eng = dict(cfg["engine"])
    vocab = cfg["vocab_size"]
    model = build_model(ctx)
    ctx.log("model built with the benchmark's weights")

    lg = None
    replica = LocalReplica("r0", lambda: LLMEngine(model, **eng))
    router = FleetRouter([replica], probe_timeout_s=1200,
                         affinity_block_size=eng["block_size"])
    gateway = None
    tracer = Tracing(ctx) if ctx.trace else None
    try:
        router.start(wait_healthy_s=1200)
        if replica.state.value != "healthy":
            raise RuntimeError(f"replica state {replica.state.value}")
        gateway = Gateway(router, cancel_on_disconnect=True).start()
        engine = replica.engine
        warm_up(ctx, gateway, vocab)

        lg = loadgen.LoadGenProcess(gateway.host, gateway.port, mix, vocab,
                                    ctx.seed)
        lg.start()
        if mix["loop"] == "closed":
            lg.wait_first_tokens()
        else:
            time.sleep(float(mix.get("ramp_s", 5)))
        seconds = ctx.seconds
        if tracer is not None:
            seconds = min(seconds, float(mix.get("trace_seconds", 8)))
            tracer.start()
        compiles0 = ctx.compiles.n
        stats0 = engine.stats()
        t_open = time.monotonic()
        setup_s = t_open - ctx.t0
        ctx.log(f"window opens after {setup_s:.1f} s of set-up")
        time.sleep(seconds)
        t_close = time.monotonic()
        compiles_in_window = ctx.compiles.n - compiles0
        trace = tracer.stop() if tracer is not None else None
        lg.stop()
        ctx.log("window closed, load stopped")
        stats1 = engine.stats()
        mem_peak = harness.memory_peak_bytes()
    finally:
        if lg is not None:
            lg.kill()
        if gateway is not None:
            gateway.stop()
            ctx.log("gateway stopped")
        router.close()
    ctx.log("served stack stopped")

    acct = window.account(lg.records, t_open, t_close)
    prefix_hits = (stats1["prefix_cache"]["hits"]
                   - stats0["prefix_cache"]["hits"])
    ctx.log(f"window: {acct['attempted']} requests attempted, "
            f"{acct['finished']} finished, {acct['failed']} failed, "
            f"{acct['cancelled']} cancelled at the close, {acct['tokens']} "
            f"tokens; engine failed {stats1['num_failed']}, preempted "
            f"{stats1['num_preemptions']}, prefix hits in the window "
            f"{prefix_hits}, prefill traces {stats1['prefill_traces']}, "
            f"decode traces {stats1['decode_traces']}, compilations in the "
            f"window {compiles_in_window}")
    if not mix.get("shared_prefix") and prefix_hits:
        raise RuntimeError(f"{prefix_hits} prefix-cache hits in a mix that "
                           f"shares no prefix")

    done = [r for r in lg.records
            if r.status == "ok" and t_open <= (r.t_end or 0) < t_close]
    wrong = [r for r in done if len(r.tokens) != r.max_tokens
             or r.finish_reason != "length"]
    # free the program's state before the reference takes the chip
    engine.cache.pool = None
    engine.params = engine.buffers = None
    for p in list(model.parameters()) + list(model.buffers()):
        p._value = None
    del engine, replica, router, gateway, model
    gc.collect()

    chk = mix["check"]
    gap, control_gap, n_tok = reference_gaps(ctx, done, chk)
    checks = {
        "logit_gap_max": harness.check(
            gap["max"], chk["limits"]["logit_gap_max"]),
        "logit_gap_mean": harness.check(
            gap["mean"], chk["limits"]["logit_gap_mean"]),
        "wrong_length": harness.check(len(wrong), 0, "eq"),
        "compiles_in_window": harness.check(compiles_in_window, 0, "eq"),
        "compared_tokens": harness.check(n_tok, chk["limits"].get(
            "compared_tokens_min", 1), "min"),
    }
    e2e = {
        "output_tokens_per_s": acct["tokens"] / acct["window_s"],
        "itl_p95_ms": window.percentile(acct["itl_ms"], 95),
        "setup_s": setup_s,
    }
    ctx.log(f"samples: {len(acct['ttft_ms'])} first tokens, "
            f"{len(acct['itl_ms'])} gaps, {len(acct['lag_ms'])} sends; "
            f"backlog: {_backlog(lg.records, t_open, t_close)}")
    facts = dict(acct)
    facts.update(stats=stats1, driver="serve")
    out = {"e2e": e2e, "attempted": acct["attempted"],
           "failed": acct["failed"], "checks": checks, "trace": trace,
           "facts": facts, "memory_peak_bytes": mem_peak}
    if control_gap is not None:
        out["control"] = {"logit_gap_max.int8": control_gap["max"],
                          "logit_gap_mean.int8": control_gap["mean"]}
    return out


def _backlog(records, t_open, t_close):
    """Requests sent and without a first token yet, at the open, the middle
    and the close of the window, and the median first-token time of each
    half: a backlog that grows shows here."""
    def waiting(t):
        return sum(1 for r in records if r.t_send is not None and r.t_send <= t
                   and (not r.t_tokens or r.t_tokens[0] > t)
                   and (r.t_end is None or r.t_end > t or r.t_tokens))
    mid = (t_open + t_close) / 2
    halves = []
    for lo, hi in ((t_open, mid), (mid, t_close)):
        v = [(r.t_tokens[0] - r.t_due) * 1e3 for r in records
             if r.t_tokens and lo <= r.t_tokens[0] < hi]
        halves.append(round(window.percentile(v, 50) or 0.0, 1))
    return {"waiting": [waiting(t) for t in (t_open, mid, t_close)],
            "ttft_p50_ms_by_half": halves}


def sample_requests(done, n, seed):
    """``n`` finished requests drawn from the seed, the longest among them."""
    if not done:
        return []
    done = sorted(done, key=lambda r: r.idx)
    longest = max(done, key=lambda r: len(r.prompt) + len(r.tokens))
    rest = [r for r in done if r is not longest]
    rng = np.random.RandomState([seed & 0xFFFFFFFF, seed >> 32, 5])
    pick = rng.permutation(len(rest))[:max(n - 1, 0)]
    return [longest] + [rest[i] for i in sorted(pick)]


def reference_gaps(ctx, done, chk):
    """Over the sampled requests, the gap by which each served token's
    reference logit lies below the reference's best: its widest and its mean
    (``{"max", "mean"}``); with ``ctx.control`` the same for the token the
    int8 control puts first at each position. Also the token count."""
    import jax
    import jax.numpy as jnp

    ref = ctx.reference
    cfg = ctx.cfg
    sample = sample_requests(done, int(chk["requests"]), ctx.seed)
    if not sample:
        return {"max": None, "mean": None}, None, 0
    pad, rows = int(chk["pad_to"]), int(chk["rows"])
    w = weights_mod.make_weights(ctx.arch.shapes(cfg), ctx.seed, cfg["dtype"])
    control = ctx.control

    @jax.jit
    def gaps(w, tokens, nxt):
        lg = ref.logits(cfg, w, tokens)
        best = jnp.max(lg, axis=-1)
        served = jnp.take_along_axis(lg, nxt[..., None], axis=-1)[..., 0]
        if not control:
            return best - served, jnp.zeros_like(best)
        low = ref.logits(cfg, w, tokens, ref.int8_linear)
        first = jnp.argmax(low, axis=-1)
        at_first = jnp.take_along_axis(lg, first[..., None], axis=-1)[..., 0]
        return best - served, best - at_first

    t = time.monotonic()
    served, lowered = [], []
    for at in range(0, len(sample), rows):
        block = sample[at:at + rows]
        tokens = np.zeros((rows, pad), np.int32)
        nxt = np.zeros((rows, pad), np.int32)
        mask = np.zeros((rows, pad), bool)
        for i, r in enumerate(block):
            seq = r.prompt + r.tokens
            if len(seq) - 1 > pad:
                raise ValueError(f"request of {len(seq)} tokens, pad_to {pad}")
            tokens[i, :len(seq) - 1] = seq[:-1]
            p = len(r.prompt)
            nxt[i, p - 1:len(seq) - 1] = r.tokens
            mask[i, p - 1:len(seq) - 1] = True
        g, gc_ = gaps(w, jnp.asarray(tokens), jnp.asarray(nxt))
        served.append(np.asarray(g)[mask])
        lowered.append(np.asarray(gc_)[mask])

    def reduce(parts):
        v = np.concatenate(parts).astype(np.float64)
        return {"max": float(v.max()), "mean": float(v.mean())}

    gap = reduce(served)
    control_gap = reduce(lowered) if control else None
    n_tok = int(sum(len(v) for v in served))
    ctx.log(f"reference over {len(sample)} requests, {n_tok} served tokens, "
            f"in {time.monotonic() - t:.1f} s: gap {gap}"
            + (f", int8 control {control_gap}" if control else ""))
    return gap, control_gap, n_tok
