"""Pretty-print a telemetry registry snapshot JSON as tables, or diff two.

The snapshot is what ``telemetry.registry().snapshot_json(path)`` writes —
this tool turns it into something eyeballable:

    python tools/metrics_dump.py METRICS.json [--filter serving_]
    python tools/metrics_dump.py --diff A.json B.json [--filter store_]
    python tools/metrics_dump.py --watch 2 http://127.0.0.1:8000/metrics

``--watch SEC`` is the live mode over a *running* gateway: the source may
be a ``/metrics`` URL (the Prometheus text exposition is parsed back into
snapshot form) or a snapshot-JSON path that keeps being rewritten. The
first refresh pretty-prints the full snapshot; every later refresh prints
the ``--diff`` view against the previous one — counter rates, histogram
interval means, gauge transitions — so it reads like ``top`` for the
serving plane.

Counters and gauges print one row per labeled series; histograms print
count / sum / mean plus a p50/p90/p99 estimate interpolated from the
cumulative bucket counts (estimates, bounded by bucket resolution —
exactly what Prometheus's ``histogram_quantile`` would report).

``--diff`` prints counter/histogram deltas between two snapshots, plus
per-second rates when both carry a ``__meta__.wall_time`` stamp (snapshots
do since PR 6) — the way to read the periodic per-rank snapshots the
cluster plane publishes (``telemetry.cluster``): grab two, diff them, and
the deltas are that rank's traffic over the interval. Gauges print the
last-value transition with its signed delta, ``a -> b (+d)`` — how a
memory watermark (``memory_live_bytes{tag=...}``) or queue depth moved
over the interval, not just where it ended.

Histogram series may carry **exemplar annotations** (PR 11: trace-id
exemplars on the serving TTFT/TPOT histograms — an ``exemplars`` key next
to ``buckets``, and OpenMetrics ``# {...}`` suffixes in the text
exposition). Both modes tolerate them: pretty-print shows the
highest-bucket exemplar's trace id next to the percentile row (the "p99
culprit" link), ``--diff`` ignores them, and unknown keys on a series —
today's exemplars or tomorrow's annotations — are never mis-parsed as
bucket data.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
import time
import urllib.request


def _quantile(buckets: dict, count: int, q: float):
    """Estimate the q-quantile from cumulative {le: count} buckets by
    linear interpolation inside the containing bucket (the
    histogram_quantile convention; +Inf-bucket hits clamp to the last
    finite edge)."""
    if not count:
        return None
    target = q * count
    edges = sorted((float(e), c) for e, c in buckets.items())
    prev_edge, prev_cum = 0.0, 0
    for edge, cum in edges:
        if cum >= target:
            span = cum - prev_cum
            frac = (target - prev_cum) / span if span else 1.0
            return prev_edge + frac * (edge - prev_edge)
        prev_edge, prev_cum = edge, cum
    return edges[-1][0] if edges else None


def _labelstr(labels: dict) -> str:
    return ",".join(f"{k}={v}" for k, v in labels.items()) or "-"


def _exemplar_note(s: dict) -> str:
    """The highest-bucket exemplar's identity, if the series carries
    exemplar annotations — the trace id behind the worst observation."""
    exs = s.get("exemplars")
    if not isinstance(exs, dict) or not exs:
        return ""
    try:
        edge = max(exs, key=lambda e: float(e))
    except (TypeError, ValueError):
        return ""
    labels = (exs[edge] or {}).get("labels") or {}
    if not labels:
        return ""
    return "  ex:" + ",".join(f"{k}={v}" for k, v in labels.items())


def format_snapshot(snap: dict, name_filter: str = "") -> str:
    lines = []
    scalars = []
    hists = []
    bad_fams = []
    for name, fam in sorted(snap.items()):
        if name.startswith("__"):        # __meta__ capture stamp
            continue
        if name_filter and name_filter not in name:
            continue
        if not isinstance(fam, dict):    # unknown family annotation:
            bad_fams.append(name)        # skipped, but never invisibly
            continue
        for s in fam.get("series", []):
            if fam.get("type") == "histogram":
                hists.append((name, s))
            else:
                scalars.append((name, fam.get("type", "?"), s))
    if scalars:
        w = max(len(n) for n, _, _ in scalars)
        lines.append(f"{'metric':<{w}}  {'type':<7} {'labels':<24} value")
        lines.append("-" * (w + 46))
        for name, kind, s in scalars:
            v = s.get("value", 0)
            vs = f"{v:.6g}" if isinstance(v, float) else str(v)
            lines.append(
                f"{name:<{w}}  {kind:<7} "
                f"{_labelstr(s.get('labels', {})):<24} {vs}")
    if hists:
        if scalars:
            lines.append("")
        w = max(len(n) for n, _ in hists)
        lines.append(f"{'histogram':<{w}}  {'labels':<24} {'count':>8} "
                     f"{'mean':>12} {'p50':>12} {'p90':>12} {'p99':>12}")
        lines.append("-" * (w + 86))
        for name, s in hists:
            cnt = s.get("count", 0)
            buckets = s.get("buckets", {})

            def fmt(x):
                return f"{x:.6g}" if x is not None else "-"

            lines.append(
                f"{name:<{w}}  {_labelstr(s.get('labels', {})):<24} "
                f"{cnt:>8} "
                f"{fmt(s.get('mean')):>12} "
                f"{fmt(_quantile(buckets, cnt, 0.5)):>12} "
                f"{fmt(_quantile(buckets, cnt, 0.9)):>12} "
                f"{fmt(_quantile(buckets, cnt, 0.99)):>12}"
                f"{_exemplar_note(s)}")
    if not lines:
        lines.append("(no metrics matched)")
    if bad_fams:
        lines.append(f"tool_parse_errors: {len(bad_fams)} "
                     f"(unparseable families skipped: "
                     f"{', '.join(bad_fams)})")
    return "\n".join(lines)


def _series_map(fam: dict) -> dict:
    """{frozen label tuple: series} for positional-independent matching."""
    return {tuple(sorted(s.get("labels", {}).items())): s
            for s in fam.get("series", [])}


def format_diff(a: dict, b: dict, name_filter: str = "") -> str:
    """Counter/histogram deltas (and rates, when both snapshots carry
    ``__meta__.wall_time``) from snapshot ``a`` to ``b``; gauges as
    ``a -> b (+delta)``. Series absent from ``a`` diff against zero
    (counters/histograms) or show ``-`` (gauges); zero-delta rows are
    suppressed."""
    dt = None
    try:
        dt = (float(b["__meta__"]["wall_time"])
              - float(a["__meta__"]["wall_time"]))
        if dt <= 0:
            dt = None
    except (KeyError, TypeError, ValueError):
        pass
    lines = [f"interval: {dt:.3f}s" if dt else
             "interval: unknown (no __meta__.wall_time; rates omitted)"]
    rows = []
    bad_fams = []
    for name, fam in sorted(b.items()):
        if name.startswith("__"):
            continue
        if name_filter and name_filter not in name:
            continue
        if not isinstance(fam, dict):    # a row that would silently vanish
            bad_fams.append(name)
            continue
        old = _series_map(a.get(name, {"series": []}))
        for key, s in sorted(_series_map(fam).items()):
            o = old.get(key)
            lbl = _labelstr(dict(key))
            if fam.get("type") == "histogram":
                # exemplar annotations (and any future per-series keys)
                # ride along on the series; only count/sum are diffed
                dc = s.get("count", 0) - (o.get("count", 0) if o else 0)
                ds = s.get("sum", 0.0) - (o.get("sum", 0.0) if o else 0.0)
                if dc == 0 and ds == 0:
                    continue
                rate = f" {dc / dt:10.4g}/s" if dt else ""
                mean = (f" mean={ds / dc:.6g}s" if dc
                        else f" sum{ds:+.6g}s")
                rows.append(f"{name:<40} {lbl:<28} +{dc:<10}{rate}{mean}")
            elif fam.get("type") == "counter":
                dv = s.get("value", 0.0) - (o.get("value", 0.0) if o else 0.0)
                if dv == 0:
                    continue
                rate = f" {dv / dt:10.4g}/s" if dt else ""
                rows.append(f"{name:<40} {lbl:<28} +{dv:<10.6g}{rate}")
            else:
                # gauges: last-value transition + signed delta (a series
                # absent from A shows "-" and no delta — nothing to
                # subtract from)
                va = o.get("value") if o else None
                vb = s.get("value", 0.0)
                if o is not None and va == vb:
                    continue
                frm = f"{va:.6g}" if va is not None else "-"
                dlt = (f" ({vb - va:+.6g})"
                       if va is not None else "")
                rows.append(f"{name:<40} {lbl:<28} {frm} -> "
                            f"{vb:.6g}{dlt}")
    lines.extend(rows or ["(no changed series matched)"])
    if bad_fams:
        lines.append(f"tool_parse_errors: {len(bad_fams)} "
                     f"(unparseable families skipped: "
                     f"{', '.join(bad_fams)})")
    return "\n".join(lines)


_LABELS_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')
_LINE_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})?\s+(\S+)')


def _parse_value(v: str) -> float:
    if v == "NaN":
        return float("nan")
    if v == "+Inf":
        return float("inf")
    if v == "-Inf":
        return float("-inf")
    return float(v)


def parse_prometheus_text(text: str) -> dict:
    """Parse the Prometheus text exposition back into the registry
    snapshot-dict shape (so ``format_snapshot`` / ``format_diff`` work on
    a live gateway's ``/metrics`` body). Histogram ``_bucket`` /``_sum``/
    ``_count`` series fold back into one series per base label set;
    OpenMetrics exemplar suffixes (``# {...}``) are stripped. The
    returned dict carries a fresh ``__meta__.wall_time`` stamp (the
    scrape time) so two parses diff into rates."""
    types: dict[str, str] = {}
    helps: dict[str, str] = {}
    # family -> {label key tuple -> series dict}
    fams: dict[str, dict] = {}

    def series(fam: str, labels: dict) -> dict:
        key = tuple(sorted(labels.items()))
        return fams.setdefault(fam, {}).setdefault(
            key, {"labels": dict(labels)})

    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        if line.startswith("#"):
            parts = line.split(None, 3)
            if len(parts) >= 4 and parts[1] == "TYPE":
                types[parts[2]] = parts[3].strip()
            elif len(parts) >= 4 and parts[1] == "HELP":
                helps[parts[2]] = parts[3]
            continue
        line = line.split(" # ", 1)[0].strip()   # exemplar suffix
        m = _LINE_RE.match(line)
        if not m:
            continue
        name, rawlabels, rawvalue = m.groups()
        try:
            value = _parse_value(rawvalue)
        except ValueError:
            continue
        labels = {k: v.replace('\\"', '"').replace("\\n", "\n")
                   .replace("\\\\", "\\")
                  for k, v in _LABELS_RE.findall(rawlabels or "")}
        base = None
        for suffix in ("_bucket", "_sum", "_count"):
            if (name.endswith(suffix)
                    and types.get(name[:-len(suffix)]) == "histogram"):
                base = name[:-len(suffix)]
                break
        if base is not None:
            le = labels.pop("le", None)
            s = series(base, labels)
            if name.endswith("_bucket"):
                if le is not None and le != "+Inf":
                    s.setdefault("buckets", {})[le] = int(value)
            elif name.endswith("_sum"):
                s["sum"] = value
            else:
                s["count"] = int(value)
        else:
            series(name, labels)["value"] = value

    out: dict = {"__meta__": {"wall_time": time.time(),
                              "source": "prometheus_text"}}
    for fam, by_key in fams.items():
        kind = types.get(fam) or (
            "counter" if fam.endswith("_total") else "gauge")
        ser = []
        for _, s in sorted(by_key.items()):
            if kind == "histogram":
                cnt = s.get("count", 0)
                s.setdefault("buckets", {})
                s.setdefault("sum", 0.0)
                s["mean"] = (s["sum"] / cnt) if cnt else None
            ser.append(s)
        out[fam] = {"type": kind, "help": helps.get(fam, ""),
                    "labels": sorted({k for s in ser
                                      for k in s.get("labels", {})}),
                    "series": ser}
    return out


def fetch_snapshot(source: str, timeout_s: float = 5.0) -> dict:
    """Load a snapshot from a URL (gateway ``/metrics`` text or any JSON
    endpoint) or a file path (snapshot JSON, or a saved exposition)."""
    if source.startswith(("http://", "https://")):
        with urllib.request.urlopen(source, timeout=timeout_s) as r:
            body = r.read().decode("utf-8", "replace")
    else:
        with open(source) as f:
            body = f.read()
    stripped = body.lstrip()
    if stripped.startswith("{"):
        return json.loads(body)
    return parse_prometheus_text(body)


def watch(source: str, interval_s: float, name_filter: str = "",
          count: int = 0, out=None) -> int:
    """Live-refresh: full snapshot first, then the --diff view between
    consecutive refreshes. ``count`` bounds the refreshes (0 = until
    interrupted). Returns 0, or 1 if the source never became readable."""
    out = out if out is not None else sys.stdout
    prev = None
    n = 0
    try:
        while True:
            try:
                snap = fetch_snapshot(source)
            except (OSError, ValueError) as e:
                print(f"[watch] source unreadable: {e}", file=out)
                if prev is None and count and n + 1 >= count:
                    return 1
                snap = None
            if snap is not None:
                stamp = time.strftime("%H:%M:%S")
                if prev is None:
                    print(f"--- {stamp} {source}", file=out)
                    print(format_snapshot(snap, name_filter), file=out)
                else:
                    print(f"\n--- {stamp} (+{interval_s:g}s)", file=out)
                    print(format_diff(prev, snap, name_filter), file=out)
                prev = snap
            n += 1
            if count and n >= count:
                return 0
            time.sleep(interval_s)
    except KeyboardInterrupt:
        return 0


def _load(path: str):
    with open(path) as f:
        return json.load(f)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("snapshot", nargs="?", default=None,
                    help="registry snapshot JSON (snapshot_json)")
    ap.add_argument("--diff", nargs=2, metavar=("A", "B"), default=None,
                    help="print counter deltas and rates from snapshot A "
                         "to snapshot B instead of pretty-printing one")
    ap.add_argument("--filter", default="",
                    help="only metric names containing this substring")
    ap.add_argument("--watch", type=float, metavar="SEC", default=None,
                    help="live mode: refresh the snapshot every SEC from "
                         "the source (a /metrics URL or a snapshot path) "
                         "and print the rate diff between refreshes")
    ap.add_argument("--count", type=int, default=0,
                    help="with --watch: stop after N refreshes (0 = "
                         "until ^C)")
    args = ap.parse_args(argv)
    if args.watch is not None:
        if args.snapshot is None or args.diff is not None:
            print("--watch takes a source (URL or path), not --diff",
                  file=sys.stderr)
            return 2
        return watch(args.snapshot, args.watch, args.filter, args.count)
    if (args.snapshot is None) == (args.diff is None):
        print("give exactly one of: a snapshot path, or --diff A B",
              file=sys.stderr)
        return 2
    try:
        if args.diff:
            print(format_diff(_load(args.diff[0]), _load(args.diff[1]),
                              args.filter))
        else:
            print(format_snapshot(_load(args.snapshot), args.filter))
    except (OSError, json.JSONDecodeError) as e:
        print(f"cannot read snapshot: {e}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
