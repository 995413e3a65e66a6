"""Traffic generation and window accounting."""
import collections

import pytest

from benchmark.lib import traffic, window

MIX = {"loop": "open", "arrival": {"kind": "poisson", "rate_qps": 4.0},
       "prompt_len": {"kind": "lognormal", "median": 192, "sigma": 0.5,
                      "min": 65, "max": 512},
       "output_len": {"kind": "lognormal", "median": 160, "sigma": 0.4,
                      "min": 64, "max": 384},
       "round": 16}


def _take(mix, seed, n):
    src = traffic.RequestSource(mix, 32768, seed)
    return [src.next() for _ in range(n)]


def _key(r):
    return (tuple(r.prompt), r.max_tokens, r.due_s)


def test_one_seed_one_traffic_two_seeds_two():
    a, b, c = _take(MIX, 2**31 + 5, 40), _take(MIX, 2**31 + 5, 40), _take(MIX, 6, 40)
    assert [_key(r) for r in a] == [_key(r) for r in b]
    assert [_key(r) for r in a] != [_key(r) for r in c]
    assert all(65 <= len(r.prompt) <= 512 and 64 <= r.max_tokens <= 384
               and all(1 <= t < 32768 for t in r.prompt) for r in a)


@pytest.mark.parametrize("field", ["prompt", "output", "gap"])
def test_every_seed_gets_the_same_sizes_in_another_order(field):
    def sizes(seed):
        rs = _take(MIX, seed, 32)       # two whole rounds
        dues = [0.0] + [r.due_s for r in rs]
        return {"prompt": [len(r.prompt) for r in rs],
                "output": [r.max_tokens for r in rs],
                "gap": [round(b - a, 9) for a, b in zip(dues, dues[1:])]}[field]
    a, b = sizes(1), sizes(2)
    assert a != b
    assert collections.Counter(a) == collections.Counter(b)


def test_quantiles_are_clamped_and_centred():
    q = traffic.quantiles(MIX["prompt_len"], 64)
    assert min(q) >= 65 and max(q) <= 512 and q == sorted(q)
    assert abs(q[31] - 192) <= 4 and abs(q[32] - 192) <= 4
    gaps = traffic.gap_quantiles(MIX["arrival"], 1000)
    assert sum(gaps) / 1000 == pytest.approx(0.25, rel=0.01)


def test_first_wave_cut_spreads_the_first_outputs():
    mix = dict(MIX, loop="closed", clients=8, first_wave_cut=True)
    first = _take(mix, 3, 8)
    uncut = _take(dict(mix, first_wave_cut=False), 3, 8)
    assert all(a.max_tokens <= b.max_tokens for a, b in zip(first, uncut))
    assert len({r.max_tokens for r in first}) > 4


def test_train_batches_from_the_seed():
    job = {"batch": 2, "seq": 16, "buffers": 3}
    a, b = traffic.train_batches(job, 100, 9), traffic.train_batches(job, 100, 9)
    c = traffic.train_batches(job, 100, 10)
    assert all((x1 == x2).all() and (y1 == y2).all()
               for (x1, y1), (x2, y2) in zip(a, b))
    assert any((x1 != x2).any() for (x1, _), (x2, _) in zip(a, c))
    rows = {tuple(r) for x, _ in a for r in x}
    assert len(rows) == 6          # every row differs


def _req(idx, prompt_len, t_due, t_send, t_tokens, status, t_end, reason="length"):
    r = traffic.Request(idx, [1] * prompt_len, len(t_tokens) or 4)
    r.t_due, r.t_send, r.t_tokens = t_due, t_send, list(t_tokens)
    r.tokens = [7] * len(t_tokens)
    r.status, r.t_end, r.finish_reason = status, t_end, reason
    return r


def test_window_accounting_on_the_edges():
    # window [10, 20)
    recs = [
        # finished before the window opened: not of the window
        _req(0, 5, 1.0, 1.0, [2.0, 3.0], "ok", 3.0),
        # straddles the open: first token before it, two tokens inside
        _req(1, 8, 8.0, 8.0, [9.0, 10.0, 11.5], "ok", 11.5),
        # wholly inside; sent 0.25 s late
        _req(2, 6, 12.0, 12.25, [13.0, 13.5, 14.5], "ok", 14.5),
        # cut by the close: a token exactly on the close is outside
        _req(3, 4, 18.0, 18.0, [19.0, 20.0], "cancelled", 20.5, None),
        # refused inside the window
        _req(4, 4, 15.0, 15.0, [], "failed", 15.1, None),
        # due after the close: never of the window
        _req(5, 4, 21.0, 21.0, [], "cancelled", 21.1, None),
    ]
    a = window.account(recs, 10.0, 20.0)
    assert a["tokens"] == 2 + 3 + 1
    assert a["attempted"] == 4 and a["failed"] == 1
    assert a["finished"] == 2 and a["cancelled"] == 1
    # first tokens inside: requests 2 (1.0 s from due) and 3 (1.0 s), and the
    # refused one counts as the whole window
    assert sorted(a["ttft_ms"]) == [1000.0, 1000.0, 10000.0]
    # gaps that end inside: 9->10, 10->11.5, 13->13.5, 13.5->14.5
    assert sorted(a["itl_ms"]) == [500.0, 1000.0, 1000.0, 1500.0]
    assert a["lag_ms"] == [250.0, 0.0, 0.0]
    # decode tokens see prompt + index positions
    assert sorted(a["decode_contexts"]) == [7, 8, 9, 10]
    assert sorted(a["prefill_lens"]) == [4, 6]


@pytest.mark.parametrize("vals,q,want", [
    ([1, 2, 3, 4, 5], 50, 3), ([1, 2, 3, 4], 50, 2.5), ([10], 95, 10),
    (list(range(101)), 90, 90), ([], 90, None)])
def test_percentile(vals, q, want):
    assert window.percentile(vals, q) == want


@pytest.fixture
def slow_sse_server():
    """An SSE server that streams a token every 20 ms, as the gateway does."""
    import http.server
    import json
    import threading
    import time

    class Handler(http.server.BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def do_POST(self):
            body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Connection", "close")
            self.end_headers()
            try:
                for i in range(body["max_tokens"]):
                    self.wfile.write(b"data: " + json.dumps({"choices": [
                        {"token_ids": [i], "finish_reason": None}]}).encode()
                        + b"\n\n")
                    self.wfile.flush()
                    time.sleep(0.02)
                self.wfile.write(b"data: " + json.dumps({"choices": [
                    {"finish_reason": "length"}]}).encode()
                    + b"\n\ndata: [DONE]\n\n")
            except OSError:
                pass

        def log_message(self, *a):
            pass

    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    yield srv.server_address
    srv.shutdown()


@pytest.mark.parametrize("loop", ["closed", "open"])
def test_load_generator_process_and_the_close_of_the_window(slow_sse_server, loop):
    """The generator in its own process: requests cut by the close are
    cancelled at once, not failed and not waited for; finished ones carry a
    stamp for every token; the parent makes the prompts again from the seed."""
    import time

    from benchmark.lib import loadgen

    host, port = slow_sse_server
    mix = {"loop": loop, "clients": 3, "round": 4,
           "arrival": {"kind": "uniform", "rate_qps": 20.0},
           "prompt_len": {"kind": "uniform", "min": 3, "max": 9},
           "output_len": {"kind": "fixed", "value": 15}}
    lg = loadgen.LoadGenProcess(host, port, mix, 100, 2**31 + 3)
    t0 = lg.start()
    if loop == "closed":
        lg.wait_first_tokens()
    time.sleep(1.0)
    t = time.monotonic()
    records = lg.stop()
    assert time.monotonic() - t < 5.0
    assert lg.proc.returncode == 0
    by = collections.Counter(r.status for r in records)
    assert by["failed"] == 0 and by["ok"] >= 3 and by["cancelled"] >= 1
    src = traffic.RequestSource(mix, 100, 2**31 + 3)
    want = [src.next() for _ in range(max(r.idx for r in records) + 1)]
    for r in sorted(records, key=lambda r: r.idx):
        assert r.prompt == want[r.idx].prompt
        assert len(r.tokens) == len(r.t_tokens) <= r.max_tokens
        if r.status == "ok":
            assert r.tokens == list(range(15)) and r.finish_reason == "length"
            assert r.t_send <= r.t_tokens[0] <= r.t_tokens[-1] <= r.t_end
    a = window.account(records, t0, time.monotonic())
    assert a["failed"] == 0 and a["cancelled"] == by["cancelled"]
    if loop == "open":
        sends = sorted(r.t_due - t0 for r in records)
        assert sends[1] - sends[0] == pytest.approx(0.05, abs=1e-6)
        assert max(a["lag_ms"]) < 50.0


def test_bursty_arrivals_keep_the_rate_and_raise_the_variation():
    n = 2000
    pois = traffic.gap_quantiles({"kind": "poisson", "rate_qps": 4.0}, n)
    burst = traffic.gap_quantiles({"kind": "bursty", "rate_qps": 4.0, "cv": 3.0}, n)

    def mean_cv(g):
        m = sum(g) / len(g)
        var = sum((x - m) ** 2 for x in g) / len(g)
        return m, var ** 0.5 / m

    (m1, cv1), (m2, cv2) = mean_cv(pois), mean_cv(burst)
    assert m1 == pytest.approx(0.25) and cv1 == pytest.approx(1.0, rel=0.05)
    assert m2 == pytest.approx(0.25) and 2.5 < cv2 < 3.2
    # a round of any size offers exactly the stated rate
    assert sum(traffic.gap_quantiles({"kind": "poisson", "rate_qps": 3.0}, 15)) \
        == pytest.approx(5.0)
    with pytest.raises(ValueError):
        traffic.gap_quantiles({"kind": "bursty", "rate_qps": 4.0, "cv": 1.0}, 4)


@pytest.mark.parametrize("prefix", [{"share": 0.5, "groups": 2},
                                    {"tokens": 70, "groups": 2}])
def test_shared_prefixes_come_from_a_few_pools(prefix):
    rs = _take(dict(MIX, shared_prefix=prefix), 11, 32)
    k = 30
    heads = collections.Counter(tuple(r.prompt[:k]) for r in rs)
    assert len(heads) == 2 and min(heads.values()) >= 4
    tails = {tuple(r.prompt[-5:]) for r in rs}
    assert len(tails) == 32
    assert [len(r.prompt) for r in rs] == \
        [len(r.prompt) for r in _take(MIX, 11, 32)]


def test_seeds_differ_by_phase_and_token_ids_alone():
    """Every seed walks the same cycle of (gap, prompt length, output length)
    triples from another starting point."""
    def triples(seed):
        rs = _take(MIX, seed, 32)
        dues = [0.0] + [r.due_s for r in rs]
        return [(round(b - a, 9), len(r.prompt), r.max_tokens)
                for a, b, r in zip(dues, dues[1:], rs)]

    a, b = triples(5), triples(2**31 + 9)
    assert a[:16] == a[16:] and b[:16] == b[16:]          # the round repeats
    shifts = [k for k in range(16) if a[k:k + 16] == b[:16]]
    assert len(shifts) == 1
    rs = _take(dict(MIX, order_seed=1), 5, 16)
    assert sorted(len(r.prompt) for r in rs) == sorted(t[1] for t in a[:16])
    assert [len(r.prompt) for r in rs] != [t[1] for t in a[:16]]
