"""Reduction of a profiler trace to the few lists the readers work on.

``load_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote, with
``jax.profiler.ProfileData`` and nothing else, into a ``Trace``: per device
the operations (line "XLA Ops") and the programs (line "XLA Modules"), and
the host's annotated spans (``TraceAnnotation`` events, which the program's
``telemetry.span`` forwards while a device trace runs). All times in
nanoseconds on the profiler's clock. ``Trace.from_json`` takes the same lists
from a small recorded file, which is what the tests check by hand.
"""
from __future__ import annotations

import glob
import json
import os
import re
from dataclasses import dataclass

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_MARK = "bench.window"
# operations that only hold others, which the profiler lists too
CONTAINERS = ("while", "conditional", "call")


@dataclass(frozen=True)
class Ev:
    name: str
    start: int          # ns
    dur: int            # ns

    @property
    def end(self):
        return self.start + self.dur


class Trace:
    def __init__(self, ops, modules, host, window=None):
        """ops, modules: {device plane name: [Ev]}; host: [Ev] of annotated
        spans; window: (start, end) ns of the traced window, by default from
        the first to the last device event."""
        self.ops = {d: sorted(v, key=lambda e: e.start) for d, v in ops.items()}
        self.modules = {d: sorted(v, key=lambda e: e.start)
                        for d, v in modules.items()}
        self.host = sorted(host, key=lambda e: e.start)
        evs = [e for v in self.ops.values() for e in v] + \
              [e for v in self.modules.values() for e in v]
        if window is None and evs:
            window = (min(e.start for e in evs), max(e.end for e in evs))
        self.window = window
        if window is not None:      # what lies outside the window is not read
            lo, hi = window

            def clip(v):
                return [e for e in v if e.start >= lo and e.end <= hi]

            self.ops = {d: clip(v) for d, v in self.ops.items()}
            self.modules = {d: clip(v) for d, v in self.modules.items()}
            self.host = [h for h in self.host
                         if h.end > lo and h.start < hi and h.name != WINDOW_MARK]

    @property
    def devices(self):
        return sorted(set(self.ops) | set(self.modules))

    @property
    def window_s(self):
        return (self.window[1] - self.window[0]) / 1e9 if self.window else 0.0

    # -- persistence (tests, debugging) ------------------------------------
    def to_json(self):
        def dump(d):
            return {k: [[e.name, e.start, e.dur] for e in v]
                    for k, v in d.items()}
        return {"ops": dump(self.ops), "modules": dump(self.modules),
                "host": [[e.name, e.start, e.dur] for e in self.host],
                "window": list(self.window) if self.window else None}

    @classmethod
    def from_json(cls, doc):
        def load(d):
            return {k: [Ev(*e) for e in v] for k, v in d.items()}
        w = doc.get("window")
        return cls(load(doc["ops"]), load(doc["modules"]),
                   [Ev(*e) for e in doc["host"]], tuple(w) if w else None)


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def op_name(text):
    """The profiler names a device operation by its whole HLO instruction,
    ``%paged_attention.8 = bf16[...] custom-call(...)``: keep its name."""
    return text.split(" = ", 1)[0].lstrip("%")


def base_name(name):
    """``paged_attention.8`` -> ``paged_attention``; ``jit_decode(123)`` ->
    ``jit_decode``: the instances of one kernel or program under one name."""
    return re.sub(r"(\.\d+|\(\d+\))$", "", name)


def load_xplane(path, host_span_re=r"^[a-z_]+\.[a-z_.]+$"):
    """Read an ``.xplane.pb``. Host spans kept are those whose name matches
    ``host_span_re`` (dotted lower-case names: the program's spans, and the
    benchmark's own ``bench.window``, which sets the trace's window)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    keep = re.compile(host_span_re)
    ops, modules, host = {}, {}, []
    for plane in data.planes:
        pname = plane.name
        if pname.startswith("/device:") and "TPU" in pname.upper():
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[pname] = [Ev(op_name(e.name), int(e.start_ns),
                                     int(e.duration_ns)) for e in line.events]
                elif line.name == MODULES_LINE:
                    modules[pname] = [
                        Ev(e.name, int(e.start_ns), int(e.duration_ns))
                        for e in line.events]
        elif pname.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if keep.match(e.name):
                        host.append(Ev(e.name, int(e.start_ns),
                                       int(e.duration_ns)))
    marks = [h for h in host if h.name == WINDOW_MARK]
    window = (marks[0].start, marks[0].end) if marks else None
    return Trace(ops, modules, host, window)


# -- reductions ------------------------------------------------------------

def union_ns(events, lo=None, hi=None):
    """Total length of the union of the events' intervals, clipped."""
    total = 0
    cur_s = cur_e = None
    for e in sorted(events, key=lambda e: e.start):
        s, t = e.start, e.end
        if lo is not None:
            s, t = max(s, lo), max(t, lo)
        if hi is not None:
            s, t = min(s, hi), min(t, hi)
        if t <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, t
        else:
            cur_e = max(cur_e, t)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_s(trace):
    """Seconds in which an operation ran, averaged over the devices."""
    if not trace.ops:
        return 0.0
    lo, hi = trace.window
    per = [union_ns(v, lo, hi) for v in trace.ops.values()]
    return sum(per) / len(per) / 1e9


def matching(events_by_dev, pattern):
    rx = re.compile(pattern)
    return {d: [e for e in v if rx.search(e.name)]
            for d, v in events_by_dev.items()}


def total_s(events_by_dev):
    """Summed durations, averaged over the devices that have the line."""
    if not events_by_dev:
        return 0.0
    return sum(sum(e.dur for e in v) for v in events_by_dev.values()) \
        / len(events_by_dev) / 1e9


def durations_ms(events_by_dev):
    return [e.dur / 1e6 for v in events_by_dev.values() for e in v]


def gaps(trace, min_ns=0):
    """Idle gaps between consecutive programs of each device, with the host
    span that covers most of each: [(label, start, dur)]."""
    out = []
    for v in trace.modules.values():
        end = None
        for e in v:
            if end is not None and e.start - end > min_ns:
                out.append((_cover(trace.host, end, e.start), end,
                            e.start - end))
            end = e.end if end is None else max(end, e.end)
    return out


def _cover(host, lo, hi):
    best, best_ns = "none", 0
    for h in host:
        if h.start >= hi:
            break
        ov = min(h.end, hi) - max(h.start, lo)
        if ov > best_ns:
            best, best_ns = h.name, ov
    return best


def top_ops(trace, n=10):
    """Device operations by total time, as ``<program>/<operation>`` with the
    instances of one kernel under one name: [[name, seconds]]. Loops and
    branches are left out: the operations inside them are listed themselves."""
    acc = {}
    for dev, v in trace.ops.items():
        mods = trace.modules.get(dev, [])
        mi = 0
        for e in v:
            if base_name(e.name) in CONTAINERS:
                continue
            while mi < len(mods) and mods[mi].end <= e.start:
                mi += 1
            prog = (base_name(mods[mi].name)
                    if mi < len(mods) and mods[mi].start <= e.start else "none")
            key = f"{prog}/{base_name(e.name)}"
            acc[key] = acc.get(key, 0) + e.dur
    k = max(len(trace.ops), 1)
    return [[name, ns / k / 1e9] for name, ns in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def top_gaps(trace, n=10):
    """Idle time between programs by what the host was doing: [[label,
    seconds]], the longest first."""
    acc = {}
    for label, _, dur in gaps(trace):
        acc[label] = acc.get(label, 0) + dur
    k = max(len(trace.modules), 1)
    return [[label, ns / k / 1e9] for label, ns in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def dump_summary(path, out_path, head=40):
    """Debugging aid: every plane and line of an ``.xplane.pb`` with its most
    expensive event names, as JSON (to look at a trace by hand)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    doc = {"file_bytes": os.path.getsize(path), "planes": []}
    for plane in data.planes:
        pd = {"name": plane.name, "lines": []}
        for line in plane.lines:
            acc, first, n = {}, None, 0
            for e in line.events:
                n += 1
                a = acc.setdefault(e.name, [0, 0])
                a[0] += 1
                a[1] += e.duration_ns
                if first is None:
                    first = {"name": e.name, "start_ns": e.start_ns,
                             "duration_ns": e.duration_ns,
                             "stats": {k: str(v)[:200] for k, v in e.stats}}
            top = sorted(acc.items(), key=lambda kv: -kv[1][1])[:head]
            pd["lines"].append({"name": line.name, "events": n,
                                "first": first,
                                "top": [[k, v[0], v[1]] for k, v in top]})
        doc["planes"].append(pd)
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1)
