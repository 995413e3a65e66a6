"""Per-request trace waterfall: render ONE request's merged Chrome trace.

The fleet answers ``GET /v1/traces/<request-id>`` with the merged
per-request trace (gateway/router row + one row per replica hop, clock-
corrected — docs/OBSERVABILITY.md "Request tracing"); this tool prints it
as a phase waterfall a human can read in a terminal:

    python tools/trace_view.py TRACE.json                # a merged file
    python tools/trace_view.py --gateway HOST:PORT cmpl-7   # live fleet
    python tools/trace_view.py --gateway HOST:PORT req-ab12cd34ef56

The id can be the completion id (``cmpl-<gid>`` / ``chatcmpl-<gid>``), a
raw gid, or the ``trace_id`` from the response's ``paddle_tpu`` block (SSE
clients get it in the final chunk). ``--json`` dumps the raw merged doc
instead (pipe into a file and open in Perfetto); ``--out PATH`` saves it
alongside the rendering.

Output: a header (state, hops, failover/replay counts), the span waterfall
(one line per span: start offset, row, name, duration, salient attrs), and
the phase summary — queue / prefill / decode / SSE-flush / failover — the
five numbers that answer "where did this request's latency go".
"""
from __future__ import annotations

import argparse
import json
import sys

# span name -> waterfall phase; lifecycle spans win over live engine spans
# for the summed phase view (they cover the whole window, ticks overlap)
_PHASE_PRIMARY = {
    "replica.inbox_wait": "queue",      # router send -> engine accepted
    "queued": "queue",
    "prefill": "prefill",
    "decode": "decode",
    "gateway.sse": "sse_flush",
    "router.failover": "failover",
}
_PHASE_FALLBACK = {
    "engine.prefill": "prefill",
    "engine.decode": "decode",
    "router.replay_suppressed": "failover",
}
PHASES = ("queue", "prefill", "decode", "sse_flush", "failover")

_ATTR_HIGHLIGHTS = ("replica", "from_replica", "to_replica", "tokens",
                    "replay_suppressed", "suppress", "cached", "batch",
                    "state", "reason", "synthesized", "error")


def _fetch_gateway(endpoint: str, request_id: str) -> dict:
    import http.client

    host, _, port = endpoint.rpartition(":")
    conn = http.client.HTTPConnection(host or "127.0.0.1", int(port),
                                      timeout=30)
    conn.request("GET", f"/v1/traces/{request_id}")
    resp = conn.getresponse()
    body = resp.read()
    conn.close()
    if resp.status != 200:
        raise SystemExit(f"gateway answered {resp.status}: "
                         f"{body.decode()[:200]}")
    return json.loads(body)


def _rows(doc: dict) -> dict:
    """pid -> row label from the process_name metadata events."""
    rows = {}
    for e in doc.get("traceEvents", []):
        if e.get("ph") == "M" and e.get("name") == "process_name":
            rows[e["pid"]] = e.get("args", {}).get("name", str(e["pid"]))
    return rows


def render(doc: dict) -> str:
    meta = doc.get("otherData", {})
    rows = _rows(doc)
    spans = sorted((e for e in doc.get("traceEvents", [])
                    if e.get("ph") == "X"),
                   key=lambda e: float(e.get("ts", 0)))
    lines = []
    head = [f"request trace {meta.get('trace_id', '?')}"]
    if meta.get("gid") is not None:
        head.append(f"gid={meta['gid']}")
    if meta.get("state"):
        head.append(f"state={meta['state']}"
                    + (f"/{meta['finish_reason']}"
                       if meta.get("finish_reason") else ""))
    if meta.get("replicas"):
        head.append("hops=" + "->".join(meta["replicas"]))
    if meta.get("failovers"):
        head.append(f"failovers={meta['failovers']}")
    if meta.get("replay_suppressed"):
        head.append(f"replayed+suppressed={meta['replay_suppressed']}")
    lines.append("  ".join(head))
    if not spans:
        lines.append("(no spans)")
        return "\n".join(lines)
    t_end = max(float(e["ts"]) + float(e.get("dur", 0)) for e in spans)
    lines.append(f"total {t_end / 1e3:.1f}ms across "
                 f"{len(rows)} rows / {len(spans)} spans")
    lines.append("")
    wrow = max((len(r) for r in rows.values()), default=7)
    wname = max(len(e["name"]) for e in spans)
    for e in spans:
        args = e.get("args", {})
        hl = " ".join(f"{k}={args[k]}" for k in _ATTR_HIGHLIGHTS
                      if args.get(k) not in (None, "", False))
        bar_on = int(20 * float(e["ts"]) / t_end) if t_end else 0
        bar_len = max(1, int(20 * float(e.get("dur", 0)) / t_end)) \
            if t_end else 1
        bar = " " * bar_on + "#" * min(bar_len, 20 - bar_on)
        lines.append(
            f"  {float(e['ts']) / 1e3:9.3f}ms "
            f"{rows.get(e['pid'], str(e['pid'])):<{wrow}} "
            f"{e['name']:<{wname}} {float(e.get('dur', 0)) / 1e3:9.3f}ms "
            f"|{bar:<20}| {hl}")
    # phase summary: prefer the lifecycle spans; fall back to live spans
    # for phases the lifecycle never covered (e.g. a hop that died)
    sums: dict[str, float] = {}
    covered = set()
    for e in spans:
        ph = _PHASE_PRIMARY.get(e["name"])
        if ph:
            sums[ph] = sums.get(ph, 0.0) + float(e.get("dur", 0))
            covered.add(ph)
    for e in spans:
        ph = _PHASE_FALLBACK.get(e["name"])
        if ph and ph not in covered:
            sums[ph] = sums.get(ph, 0.0) + float(e.get("dur", 0))
    lines.append("")
    lines.append("phases: " + "  ".join(
        f"{ph}={sums.get(ph, 0.0) / 1e3:.1f}ms" for ph in PHASES
        if ph in sums or ph in ("queue", "prefill", "decode")))
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="render a per-request merged trace as a waterfall")
    ap.add_argument("target",
                    help="merged trace JSON path, or (with --gateway) a "
                         "request id: cmpl-<gid>, a gid, or a trace_id")
    ap.add_argument("--gateway", metavar="HOST:PORT", default=None,
                    help="fetch GET /v1/traces/<target> from a live "
                         "gateway instead of reading a file")
    ap.add_argument("--json", action="store_true",
                    help="print the raw merged trace JSON instead")
    ap.add_argument("--out", default=None,
                    help="also save the merged trace JSON here")
    args = ap.parse_args(argv)

    if args.gateway:
        doc = _fetch_gateway(args.gateway, args.target)
    else:
        try:
            with open(args.target) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"cannot read trace: {e}", file=sys.stderr)
            return 1
    if args.out:
        with open(args.out, "w") as f:
            json.dump(doc, f, default=str)
    if args.json:
        print(json.dumps(doc, indent=1, default=str))
    else:
        print(render(doc))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
