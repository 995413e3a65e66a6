"""The one general traffic generator. A mix is a data file of parameters.

Every seed gets the same traffic at another phase. Lengths and inter-arrival
gaps are the quantiles of their distributions, ``round`` of them; the mix's
``order_seed`` shuffles them once into one round of (gap, prompt length,
output length) triples, which repeats; ``--seed`` picks where in the round
the run starts and draws the token ids. So two seeds differ as two runs of
one seed do: the same sizes, the same clumps of arrivals, in the same cyclic
order, and a window of any length sees whole rounds of them (but for its
edges).

Serving mix (``traffic/<name>.json``)::

    {"loop": "closed" | "open",
     "clients": 32,                      # closed: callers that wait
     "first_wave_cut": true,             # closed: first request of each client
                                         # has its output budget cut to a
                                         # uniform share, so completions spread
     "arrival": {"kind": "poisson" | "uniform" | "bursty", "rate_qps": 4.0},
                                         # open; bursty also takes "cv" > 1
     "ramp_s": 6,                        # open: load offered before the window
     "prompt_len": {"kind": "lognormal", "median": .., "sigma": .., "min": .., "max": ..},
     "output_len": {...},                # kinds: fixed, uniform, lognormal
     "round": 16, "order_seed": 0,
     "shared_prefix": {"share": 0.5, "groups": 4},   # optional: that share of
                                         # each prompt (or "tokens": n of it)
                                         # comes from one of `groups` pools
     "warm_prompt_lens": [128, 256, 512],
     "check": {"requests": 8, "pad_to": 1024, "rows": 4, "limits": {...}}}
"""
from __future__ import annotations

import math
import threading
from dataclasses import dataclass, field
from statistics import NormalDist

import numpy as np


def quantiles(dist: dict, n: int) -> list:
    """The ``n`` mid-quantiles of a length distribution, as whole numbers."""
    kind = dist["kind"]
    ps = [(i + 0.5) / n for i in range(n)]
    if kind == "fixed":
        vals = [dist["value"]] * n
    elif kind == "uniform":
        vals = [dist["min"] + p * (dist["max"] - dist["min"]) for p in ps]
    elif kind == "lognormal":
        nd = NormalDist()
        vals = [dist["median"] * math.exp(dist["sigma"] * nd.inv_cdf(p))
                for p in ps]
    else:
        raise ValueError(f"unknown length kind {kind!r}")
    lo = dist.get("min", 1)
    hi = dist.get("max", max(vals))
    return [int(min(max(round(v), lo), hi)) for v in vals]


def gap_quantiles(arrival: dict, n: int) -> list:
    """The ``n`` mid-quantiles of the inter-arrival gap, in seconds, scaled
    so that they add up to ``n / rate_qps``: a round then offers exactly the
    stated rate (the mid-quantiles alone cut the tail and run 2% fast) and
    lasts a known time, so a window of whole rounds sees the same arrivals
    whatever the phase."""
    rate = float(arrival["rate_qps"])
    gaps = _gap_quantiles(arrival, n, rate)
    scale = n / rate / sum(gaps)
    return [g * scale for g in gaps]


def _gap_quantiles(arrival, n, rate):
    if arrival["kind"] == "uniform":
        return [1.0 / rate] * n
    ps = [(i + 0.5) / n for i in range(n)]
    if arrival["kind"] == "poisson":
        return [-math.log(1.0 - p) / rate for p in ps]
    if arrival["kind"] == "bursty":
        # a renewal process with hyperexponential gaps (two phases with
        # balanced means): mean 1 / rate, coefficient of variation ``cv``
        cv2 = float(arrival["cv"]) ** 2
        if cv2 <= 1.0:
            raise ValueError("a bursty arrival process needs cv > 1")
        p1 = 0.5 * (1.0 + math.sqrt((cv2 - 1.0) / (cv2 + 1.0)))
        r1, r2 = 2.0 * p1 * rate, 2.0 * (1.0 - p1) * rate

        def inv_cdf(p):
            lo, hi = 0.0, 1.0
            cdf = lambda t: 1.0 - p1 * math.exp(-r1 * t) - (1 - p1) * math.exp(-r2 * t)
            while cdf(hi) < p:
                hi *= 2.0
            for _ in range(60):
                mid = 0.5 * (lo + hi)
                lo, hi = (mid, hi) if cdf(mid) < p else (lo, mid)
            return 0.5 * (lo + hi)

        return [inv_cdf(p) for p in ps]
    raise ValueError(f"unknown arrival kind {arrival['kind']!r}")


@dataclass
class Request:
    idx: int
    prompt: list
    max_tokens: int
    due_s: float = 0.0               # open loop: offset from the first send
    # filled by the load generator (host monotonic clock, seconds)
    t_due: float | None = None
    t_send: float | None = None
    t_tokens: list = field(default_factory=list)
    tokens: list = field(default_factory=list)
    t_end: float | None = None
    status: str = "pending"          # ok | failed | cancelled
    finish_reason: str | None = None
    error: str | None = None


class RequestSource:
    """Requests in order, made a round at a time."""

    def __init__(self, traffic: dict, vocab: int, seed: int):
        self.traffic = traffic
        self.vocab = int(vocab)
        self.seed = int(seed)
        n = self.round = int(traffic.get("round", 16))
        order = np.random.RandomState(int(traffic.get("order_seed", 0)))
        self._p = order.permutation(quantiles(traffic["prompt_len"], n))
        self._o = order.permutation(quantiles(traffic["output_len"], n))
        self._g = (order.permutation(gap_quantiles(traffic["arrival"], n))
                   if traffic.get("loop") == "open" else None)
        clients = int(traffic.get("clients", 0))
        self._cut = (order.permutation(
            [(i + 0.5) / clients for i in range(clients)])
            if traffic.get("first_wave_cut") and clients else None)
        self._phase = int(self._rng(2).randint(n))
        sp = traffic.get("shared_prefix") or {}
        self._share = float(sp.get("share", 0.0))
        self._prefix_tokens = int(sp.get("tokens", 0))
        self._groups = int(sp.get("groups", 1))
        self._prefix_pool = None
        self._lock = threading.Lock()
        self._buf = []
        self._n = 0
        self._rounds = 0
        self._clock = 0.0

    def _rng(self, *salt):
        return np.random.RandomState(
            [self.seed & 0xFFFFFFFF, self.seed >> 32, *salt])

    def _prefix(self, group, n):
        if self._prefix_pool is None:
            top = max(int(self._p.max()), self._prefix_tokens)
            self._prefix_pool = self._rng(7).randint(
                1, self.vocab, (self._groups, top))
        return self._prefix_pool[group, :n].tolist()

    def _make_round(self):
        rng = self._rng(1, self._rounds)
        idx = (np.arange(self.round) + self._phase) % self.round
        p, o = self._p[idx], self._o[idx]
        ids = rng.randint(1, self.vocab, int(p.sum()))
        at = 0
        for i in range(self.round):
            n = int(p[i])
            prompt = ids[at:at + n].tolist()
            at += n
            if self._share > 0 or self._prefix_tokens:
                k = min(self._prefix_tokens or int(n * self._share), n - 1)
                prompt[:k] = self._prefix(int(rng.randint(self._groups)), k)
            out = int(o[i])
            if self._cut is not None and self._n < len(self._cut):
                # the first request of each caller: a uniform share of its
                # output budget, so that completions spread from the start
                out = max(4, int(round(out * self._cut[self._n])))
            if self._g is not None:
                self._clock += float(self._g[idx[i]])
            self._buf.append(Request(self._n, prompt, out, self._clock))
            self._n += 1
        self._rounds += 1

    def next(self) -> Request:
        with self._lock:
            if not self._buf:
                self._make_round()
            return self._buf.pop(0)


def train_batches(job: dict, vocab: int, seed: int):
    """``buffers`` batches of token ids and labels, [batch, seq] int32 each,
    every row different, from the seed."""
    rng = np.random.RandomState([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 3])
    shape = (int(job["batch"]), int(job["seq"]))
    return [(rng.randint(0, vocab, shape).astype(np.int32),
             rng.randint(0, vocab, shape).astype(np.int32))
            for _ in range(int(job.get("buffers", 4)))]
