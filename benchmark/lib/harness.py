"""One run of one cell: find what the cell names, hand it to its driver,
reduce what comes back to the result line.

The harness knows no cell, configuration, mix, metric or model by name: the
cell's configuration names its driver (``drivers/<driver>.py``), its
architecture (``arch/<arch>.py``: the program's model, the weights' leaves,
the work counts) and its reference (``reference/<reference>.py``); each
per-layer metric's file
(``metrics/<name>.json``) names its reader (``readers/<reader>.py``) and the
reader's arguments.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

from . import spec as spec_mod, trace as trace_mod


class RunContext:
    """What a driver gets."""

    def __init__(self, spec, cell, seed, seconds, trace, control, t0,
                 require_chip):
        self.spec = spec
        self.cell = spec.cell(cell)
        self.cfg = spec.config(self.cell["config"])
        self.traffic = spec.traffic(cell)
        self.seed, self.seconds = int(seed), float(seconds)
        self.trace, self.control = bool(trace), bool(control)
        self.t0 = t0
        self.require_chip = require_chip
        self.arch = spec.module("arch", self.cfg["arch"])
        self.reference = spec.module("reference", self.cfg["reference"])
        self.trace_dir = os.path.join(spec.root, ".bench_trace")
        self.compiles = CompileCounter()

    def log(self, msg):
        print(f"[bench {time.monotonic() - self.t0:7.1f}s] {msg}",
              file=sys.stderr, flush=True)


class CompileCounter:
    """Counts XLA compilations and compile-cache loads as JAX reports them."""

    def __init__(self):
        self.n = 0
        self.names = []

    def arm(self):
        import jax.monitoring

        def listen(event, duration, **kw):
            del duration, kw
            if "backend_compile" in event or "cache_retrieval" in event:
                self.n += 1
                self.names.append(event)

        jax.monitoring.register_event_duration_secs_listener(listen)
        return self


def device_info(chips, require_chip):
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_chip:
        if info["platform"] != "tpu":
            raise SystemExit(
                f"benchmark: the default backend is {info['platform']!r} "
                f"({info['kind']}), not a TPU; a CPU number is never printed "
                f"under a device metric's name")
        if info["count"] < chips:
            raise SystemExit(f"benchmark: the cell needs {chips} chip(s), "
                             f"JAX sees {info['count']}")
    return info


def memory_peak_bytes():
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks)) if peaks else 0


def apply_overrides(doc, overrides):
    """``a.b=value`` (JSON value) edits of a traffic file, for sweeps."""
    for item in overrides or ():
        key, _, val = item.partition("=")
        node = doc
        parts = key.split(".")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = json.loads(val)


def read_per_layer(ctx, run, device):
    """Each per-layer metric of the cell through its reader; a reader that
    finds nothing to read returns None and the metric is left out."""
    from . import peaks as peaks_mod

    out = {}
    facts = dict(run["facts"])
    # no chip, no peak: every share of one then finds nothing to read
    facts.update(cfg=ctx.cfg, arch=ctx.arch, traffic=ctx.traffic,
                 trace=run["trace"],
                 peaks=peaks_mod.peaks(device["kind"]) if ctx.require_chip
                 else None, chips=ctx.cell["chips"])
    for m in ctx.spec.per_layer(ctx.cell["name"]):
        mdoc = ctx.spec.load_json("metrics", m["name"])
        reader = ctx.spec.module("readers", mdoc["reader"])
        value = reader.read(facts, **mdoc.get("args", {}))
        if value is None:
            continue
        if not math.isfinite(value):
            raise ValueError(f"metric {m['name']} read {value}")
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(cell, seed, seconds, trace, *, control=False, root=spec_mod.ROOT,
             require_chip=True, t0=None, overrides=None, out=None):
    """Run one cell; print the result line to ``out``; return (rc, result)."""
    t0 = time.monotonic() if t0 is None else t0
    out = out or sys.stdout
    spec = spec_mod.Spec(root)
    ctx = RunContext(spec, cell, seed, seconds, trace, control, t0,
                     require_chip)
    apply_overrides(ctx.traffic, overrides)
    device = device_info(ctx.cell["chips"], require_chip)

    from paddle_tpu.utils import compile_cache
    cache_dir = compile_cache.enable()
    ctx.log(f"cell {cell} seed {seed} seconds {seconds} trace {int(trace)} on "
            f"{device}; compile cache {cache_dir}")
    ctx.compiles.arm()

    driver = spec.module("drivers", ctx.cfg["driver"])
    run = driver.run(ctx)

    device["memory_peak_bytes"] = run["memory_peak_bytes"]
    metrics = {}
    if not trace:
        for m in spec.end_to_end(cell):
            value = run["e2e"].get(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        tr = run["trace"]
        device["busy_s"] = trace_mod.busy_s(tr) if tr is not None else 0.0
        device["window_s"] = tr.window_s if tr is not None else 0.0
        metrics = read_per_layer(ctx, run, device)
    checks = run["checks"]
    correct = all(c["ok"] for c in checks.values())
    result = {"correct": bool(correct), "attempted": run["attempted"],
              "failed": run["failed"], "metrics": metrics, "device": device}
    if trace and run["trace"] is not None:
        result["breakdown"] = {
            "device_ops": trace_mod.top_ops(run["trace"]),
            "idle_gaps": trace_mod.top_gaps(run["trace"])}
    if run.get("control"):
        result["control"] = run["control"]
    result["checks"] = {
        k: {"value": c["value"], "limit": c["limit"]} for k, c in checks.items()}
    for k, c in checks.items():
        print(f"check {k}: {c['value']} limit {c['limit']} "
              f"{'ok' if c['ok'] else 'NOT OK'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), file=out, flush=True)
    return 0, result


def check(value, limit, kind="max"):
    """One compared number beside its limit. kind: "max" (value <= limit),
    "min" (value >= limit), "eq" (value == limit)."""
    if value is None or (isinstance(value, float) and not math.isfinite(value)):
        ok = False
    elif kind == "max":
        ok = value <= limit
    elif kind == "min":
        ok = value >= limit
    else:
        ok = value == limit
    return {"value": value, "limit": limit, "ok": bool(ok)}


def main(argv, t0):
    ap = argparse.ArgumentParser(prog="benchmark/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also read the lower-precision control and the "
                         "planted faults (calibration; never in a check)")
    ap.add_argument("--set", action="append", default=[], metavar="KEY=JSON",
                    help="override a key of the traffic file (sweeps only)")
    args = ap.parse_args(argv)
    rc, _ = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                     control=bool(args.control), t0=t0, overrides=args.set)
    return rc
