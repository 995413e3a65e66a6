"""paddle.profiler parity, TPU-native.

Reference surface: the unified host+device Profiler
(/root/reference/python/paddle/profiler/profiler.py:340 — scheduler windows,
start/stop/step, export_chrome_tracing) and the throughput Benchmark
instrument (timer.py:349 — reader_cost / batch_cost / ips via TimerHook).

TPU stance: device tracing is jax.profiler (XLA's TraceMe + TPU device
traces, viewable in TensorBoard/Perfetto/xprof) — we wrap rather than rebuild
the event collector; host annotations use jax.profiler.TraceAnnotation so
they interleave with XLA's own events in the same trace. The Benchmark math
(TimeAverager, ips) is host-side and implemented here directly. A share of
the chip's peak is not taken here: ``benchmark/run.py`` takes it, on a chip.
"""
from __future__ import annotations

import time
from enum import Enum

import jax

from .. import telemetry

__all__ = [
    "Profiler", "ProfilerTarget", "ProfilerState", "RecordEvent",
    "make_scheduler", "export_chrome_tracing", "Benchmark", "benchmark",
    "TimeAverager", "parse_trace_op_times", "format_op_table",
]


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1
    CUSTOM_DEVICE = 3
    TPU = 4  # beyond-reference: the native target here


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


def make_scheduler(*, closed: int, ready: int, record: int, repeat: int = 0,
                   skip_first: int = 0):
    """Window scheduler (reference profiler.py:114): per-step state out of
    [skip_first][closed][ready][record...] cycles."""
    period = closed + ready + record

    def scheduler(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        step -= skip_first
        if repeat > 0 and step // period >= repeat:
            return ProfilerState.CLOSED
        pos = step % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


def _default_scheduler(step: int) -> ProfilerState:
    return ProfilerState.RECORD


def export_chrome_tracing(dir_name: str, worker_name: str | None = None):
    """on_trace_ready factory (reference profiler.py:212). jax.profiler
    already writes trace.json.gz under the log dir; this returns a handler
    that records where."""

    def handle_fn(prof):
        prof._last_export_dir = dir_name

    handle_fn._dir_name = dir_name
    return handle_fn


class RecordEvent:
    """Host-side named span (reference event_tracing.h RecordEvent / python
    RecordEvent). Emits a jax.profiler.TraceAnnotation so it nests with XLA
    device events in the exported trace; also usable as a decorator."""

    def __init__(self, name: str, event_type=None):
        self.name = name
        self._ann = None
        self.begin_ns = None
        self.end_ns = None

    def begin(self):
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self.begin_ns = time.perf_counter_ns()

    def end(self):
        if self._ann is not None:
            self.end_ns = time.perf_counter_ns()
            self._ann.__exit__(None, None, None)
            self._ann = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False

    def __call__(self, fn):
        import functools

        @functools.wraps(fn)
        def wrapped(*a, **k):
            with RecordEvent(self.name):
                return fn(*a, **k)

        return wrapped


class Profiler:
    """Scheduler-windowed tracing (reference profiler.py:340).

    ``start``/``stop`` bracket a jax.profiler trace; ``step`` advances the
    scheduler and forwards throughput accounting to the Benchmark. On
    RECORD→CLOSED transitions the trace is stopped and on_trace_ready fires.
    """

    def __init__(self, *, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only: bool = False, record_shapes: bool = False,
                 profile_memory: bool = False, with_flops: bool = False):
        if callable(scheduler):
            self._scheduler = scheduler
        elif isinstance(scheduler, (tuple, list)) and len(scheduler) == 2:
            lo, hi = scheduler
            self._scheduler = make_scheduler(
                closed=max(lo, 0), ready=0, record=hi - lo, repeat=1)
        else:
            self._scheduler = _default_scheduler
        self._on_trace_ready = on_trace_ready
        self._timer_only = timer_only
        self.step_num = 0
        self.current_state = ProfilerState.CLOSED
        self._tracing = False
        self._last_export_dir = None
        self._benchmark = Benchmark()

    # -- lifecycle -------------------------------------------------------
    def _trace_dir(self):
        if self._on_trace_ready is not None and \
                getattr(self._on_trace_ready, "_dir_name", None):
            return self._on_trace_ready._dir_name
        import tempfile

        return tempfile.mkdtemp(prefix="paddle_tpu_trace_")

    def _start_trace(self):
        if not self._tracing and not self._timer_only:
            self._dir = self._trace_dir()
            jax.profiler.start_trace(self._dir)
            self._tracing = True
            # telemetry spans now forward to jax TraceAnnotations, so host
            # request/engine spans interleave with XLA events in this trace
            telemetry.set_device_trace_active(True)

    def _stop_trace(self):
        if self._tracing:
            jax.profiler.stop_trace()
            self._tracing = False
            telemetry.set_device_trace_active(False)
            self._last_export_dir = self._dir
            if self._on_trace_ready is not None:
                self._on_trace_ready(self)

    def start(self):
        self._benchmark.begin()
        self.current_state = self._scheduler(self.step_num)
        if self.current_state in (ProfilerState.READY, ProfilerState.RECORD,
                                  ProfilerState.RECORD_AND_RETURN):
            self._start_trace()
        return self

    def stop(self):
        self._benchmark.end()
        self._stop_trace()
        self.current_state = ProfilerState.CLOSED

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    def step(self, num_samples=None):
        self._benchmark.step(num_samples)
        self.step_num += 1
        new_state = self._scheduler(self.step_num)
        recording = self.current_state in (
            ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN,
            ProfilerState.READY)
        should_record = new_state in (
            ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN,
            ProfilerState.READY)
        if recording and not should_record:
            self._stop_trace()
        elif should_record and not recording:
            self._start_trace()
        self.current_state = new_state

    def step_info(self, unit="samples"):
        return self._benchmark.step_info(unit)

    def export(self, path=None, format="json"):
        """jax traces are written at stop time. With ``path``, copy the
        last trace directory there (the reference API contract: export
        lands where the caller asked) and return ``path``; without it,
        return the trace dir. Only chrome-trace ``format="json"`` exists
        on this backend — anything else is an explicit error, not a
        silent ignore."""
        if format not in (None, "json"):
            raise ValueError(
                f"unsupported export format {format!r}: jax.profiler "
                f"writes chrome-trace json (pass format='json')")
        if path is None:
            return self._last_export_dir
        if self._last_export_dir is None:
            raise RuntimeError(
                "no trace to export: start()/stop() a recording window "
                "first (timer_only profilers never record traces)")
        import shutil

        shutil.copytree(self._last_export_dir, path, dirs_exist_ok=True)
        return path

    def summary(self, max_rows=10, print_table=True, **kwargs):
        """Throughput report + per-op time tables parsed from the exported
        trace (reference profiler_statistic.py:1 summary tables). Returns
        the benchmark report dict extended with ``op_summary`` (device ops)
        and ``host_summary`` rows; prints the formatted table like the
        reference unless ``print_table=False``."""
        report = self._benchmark.report()
        if self._last_export_dir is not None:
            dev_rows, host_rows = parse_trace_op_times(self._last_export_dir)
            report["op_summary"] = dev_rows[:max_rows]
            report["host_summary"] = host_rows[:max_rows]
            report["trace_files_seen"] = dev_rows.meta["files_seen"]
            report["trace_files_skipped"] = dev_rows.meta["files_skipped"]
            if print_table and (dev_rows or host_rows):
                print(format_op_table(dev_rows[:max_rows],
                                      host_rows[:max_rows]))
            if print_table and dev_rows.meta["files_skipped"]:
                print(f"!! {dev_rows.meta['files_skipped']} of "
                      f"{dev_rows.meta['files_seen']} trace files could "
                      f"not be parsed (see parse_trace_op_times(...).meta)")
        return report


# ---------------------------------------------------------------------------
# Benchmark (ips instrument) — reference timer.py:349
# ---------------------------------------------------------------------------

class TimeAverager:
    """reference timer.py:302 — running averages with sample accounting."""

    def __init__(self):
        self.reset()

    def reset(self):
        self._total_time = 0.0
        self._count = 0
        self._total_samples = 0

    def record(self, usetime, num_samples=None):
        self._total_time += usetime
        self._count += 1
        if num_samples:
            self._total_samples += num_samples

    def get_average(self):
        return self._total_time / self._count if self._count else 0.0

    def get_ips_average(self):
        if not self._total_samples or self._total_time == 0.0:
            return 0.0
        return self._total_samples / self._total_time

    @property
    def count(self):
        return self._count


class Benchmark:
    """reader_cost / batch_cost / ips throughput instrument
    (reference timer.py:349; hapi and the bench harness consume it)."""

    def __init__(self):
        self.reader = TimeAverager()
        self.batch = TimeAverager()
        self._reader_t0 = None
        self._batch_t0 = None
        self.num_samples = None
        self.speed_unit = "samples/s"

    def begin(self):
        now = time.perf_counter()
        self._batch_t0 = now
        self._reader_t0 = now

    def before_reader(self):
        self._reader_t0 = time.perf_counter()

    def after_reader(self):
        if self._reader_t0 is not None:
            self.reader.record(time.perf_counter() - self._reader_t0)

    def step(self, num_samples=None):
        """Close out one step (reference Benchmark.step)."""
        now = time.perf_counter()
        if self._batch_t0 is not None:
            self.batch.record(now - self._batch_t0, num_samples)
        self._batch_t0 = now
        self.num_samples = num_samples

    after_step = step

    def end(self):
        self._batch_t0 = None

    # -- reporting -------------------------------------------------------
    def reader_average(self):
        return self.reader.get_average()

    def batch_average(self):
        return self.batch.get_average()

    def speed_average(self):
        return self.batch.get_ips_average()

    def step_info(self, unit="samples"):
        msg = ""
        if self.reader.count:
            msg += f" reader_cost: {self.reader_average():.5f} s"
        if self.batch.count:
            msg += f" batch_cost: {self.batch_average():.5f} s"
        ips = self.speed_average()
        if ips:
            msg += f" ips: {ips:.3f} {unit}/s"
        return msg

    def report(self):
        return {
            "reader_cost": self.reader_average(),
            "batch_cost": self.batch_average(),
            "ips": self.speed_average(),
        }

    def reset(self):
        self.reader.reset()
        self.batch.reset()
        # stale step anchors would make the first step() after a reset
        # record the whole inter-reset gap as one bogus batch interval
        self._reader_t0 = None
        self._batch_t0 = None
        self.num_samples = None


# ---------------------------------------------------------------------------
# Per-op summary tables from the exported trace
# (reference python/paddle/profiler/profiler_statistic.py:1)
# ---------------------------------------------------------------------------

class _OpRows(list):
    """Row list with parse provenance attached: ``rows.meta`` counts the
    trace files seen vs skipped (unreadable/corrupt), so an empty summary
    is distinguishable from a summary whose inputs all failed to parse."""

    def __init__(self, rows=(), meta=None):
        super().__init__(rows)
        self.meta = meta or {"files_seen": 0, "files_skipped": 0,
                             "skipped": []}


def parse_trace_op_times(trace_dir):
    """Aggregate the chrome trace jax.profiler exported under ``trace_dir``
    into (device_rows, host_rows): per-op name {calls, total_us, avg_us,
    pct} sorted by total time desc. Device rows come from ``/device:*``
    processes (TPU op execution); host rows are non-python-frame host spans
    (RecordEvent annotations, dispatch). Both returned lists carry a
    ``.meta`` dict — {files_seen, files_skipped, skipped: [(path, error)]}
    — naming every trace file that could not be parsed instead of silently
    dropping it."""
    import collections
    import glob
    import gzip
    import json
    import os

    files = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.trace.json.gz"))
    meta = {"files_seen": len(files), "files_skipped": 0, "skipped": []}
    dev = collections.defaultdict(lambda: [0, 0.0])
    host = collections.defaultdict(lambda: [0, 0.0])
    for f in files:
        try:
            with gzip.open(f, "rt") as fh:
                events = json.load(fh).get("traceEvents", [])
        except Exception as e:
            meta["files_skipped"] += 1
            meta["skipped"].append((f, f"{type(e).__name__}: {e}"))
            continue
        pname = {}
        for e in events:
            if e.get("ph") == "M" and e.get("name") == "process_name":
                pname[e.get("pid")] = e.get("args", {}).get("name", "")
        for e in events:
            if e.get("ph") != "X":
                continue
            name = e.get("name", "")
            if name.startswith("$"):  # python stack-frame span
                continue
            proc = pname.get(e.get("pid"), "")
            bucket = dev if "/device" in proc else host
            entry = bucket[name]
            entry[0] += 1
            entry[1] += float(e.get("dur", 0.0))

    def rows(bucket):
        total = sum(v[1] for v in bucket.values()) or 1.0
        out = [{"name": n, "calls": c, "total_us": round(t, 1),
                "avg_us": round(t / c, 2) if c else 0.0,
                "pct": round(100.0 * t / total, 2)}
               for n, (c, t) in bucket.items()]
        out.sort(key=lambda r: -r["total_us"])
        return _OpRows(out, meta)

    return rows(dev), rows(host)


def format_op_table(dev_rows, host_rows):
    """Render rows like the reference's summary tables."""
    lines = []

    def table(title, rows):
        if not rows:
            return
        lines.append(f"---- {title} " + "-" * max(0, 66 - len(title)))
        lines.append(f"{'Name':<44} {'Calls':>6} {'Total(us)':>12} "
                     f"{'Avg(us)':>10} {'Ratio(%)':>9}")
        for r in rows:
            nm = r["name"] if len(r["name"]) <= 44 else r["name"][:41] + "..."
            lines.append(f"{nm:<44} {r['calls']:>6} {r['total_us']:>12.1f} "
                         f"{r['avg_us']:>10.2f} {r['pct']:>9.2f}")

    table("Device (TPU) op summary", dev_rows)
    table("Host summary", host_rows)
    return "\n".join(lines)


_GLOBAL_BENCHMARK = Benchmark()


def benchmark() -> Benchmark:
    """Global instance (reference timer.py benchmark())."""
    return _GLOBAL_BENCHMARK
