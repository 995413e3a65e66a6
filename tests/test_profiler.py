"""paddle_tpu.profiler: Benchmark math, scheduler windows, trace lifecycle."""
import os
import time

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu import profiler as prof


def test_benchmark_ips_math():
    b = prof.Benchmark()
    b.begin()
    for _ in range(3):
        b.before_reader()
        time.sleep(0.01)
        b.after_reader()
        time.sleep(0.02)
        b.step(num_samples=100)
    b.end()
    r = b.report()
    assert r["reader_cost"] >= 0.01
    assert r["batch_cost"] >= 0.02
    # 100 samples per ~0.03s step => ips in the low thousands
    assert 100 < r["ips"] < 100 / 0.02
    assert "ips" in b.step_info("samples")


def test_make_scheduler_windows():
    sched = prof.make_scheduler(closed=1, ready=1, record=2, repeat=1,
                                skip_first=1)
    states = [sched(i) for i in range(6)]
    S = prof.ProfilerState
    assert states[0] == S.CLOSED        # skip_first
    assert states[1] == S.CLOSED        # closed window
    assert states[2] == S.READY
    assert states[3] == S.RECORD
    assert states[4] == S.RECORD_AND_RETURN
    assert states[5] == S.CLOSED        # repeat=1 exhausted


def test_profiler_trace_roundtrip(tmp_path):
    d = str(tmp_path / "trace")
    p = prof.Profiler(on_trace_ready=prof.export_chrome_tracing(d))
    p.start()
    with prof.RecordEvent("train_step"):
        jax.block_until_ready(jnp.ones((8, 8)) @ jnp.ones((8, 8)))
    p.step(num_samples=64)
    p.stop()
    assert p.export() == d
    # jax.profiler writes plugins/profile/<run>/ under the log dir
    found = [os.path.join(dp, f) for dp, _, fs in os.walk(d) for f in fs]
    assert found, "no trace files written"
    assert p.summary()["ips"] > 0


def test_record_event_as_decorator():
    @prof.RecordEvent("fn")
    def f(a):
        return a + 1

    assert f(1) == 2


class TestOpSummary:
    """Per-op summary tables parsed from the exported trace (VERDICT r3
    missing #7; reference profiler_statistic.py:1)."""

    def test_summary_has_op_tables(self, tmp_path, capsys):
        import jax.numpy as jnp

        from paddle_tpu import profiler as prof

        p = prof.Profiler(
            on_trace_ready=prof.export_chrome_tracing(str(tmp_path)))
        p.start()
        with prof.RecordEvent("op_summary_test_span"):
            x = jnp.ones((128, 128))
            for _ in range(3):
                x = jnp.tanh(x @ x)
            x.block_until_ready()
        p.step(num_samples=128)
        p.stop()
        rep = p.summary(max_rows=10)
        assert "op_summary" in rep and "host_summary" in rep
        rows = rep["host_summary"] + rep["op_summary"]
        assert rows, "no events parsed from the exported trace"
        names = [r["name"] for r in rows]
        assert any("op_summary_test_span" in n for n in names)
        for r in rows:
            assert r["calls"] >= 1 and r["total_us"] >= 0
        out = capsys.readouterr().out
        assert "summary" in out and "Calls" in out  # printed table

    def test_format_op_table(self):
        from paddle_tpu.profiler import format_op_table

        s = format_op_table(
            [{"name": "fusion.1", "calls": 3, "total_us": 10.0,
              "avg_us": 3.33, "pct": 100.0}], [])
        assert "Device (TPU) op summary" in s and "fusion.1" in s


# ---------------------------------------------------------------------------
# ISSUE 4 satellite fixes
# ---------------------------------------------------------------------------

def test_benchmark_reset_clears_step_anchors():
    """The first step() after reset() must not record the whole inter-reset
    gap as one bogus batch interval (the stale _batch_t0/_reader_t0 bug)."""
    b = prof.Benchmark()
    b.begin()
    b.step(num_samples=1)
    b.reset()
    time.sleep(0.05)            # the would-be bogus interval
    b.step(num_samples=1)       # first post-reset step: arms, records nothing
    assert b.batch.count == 0
    b.step(num_samples=1)       # second: records a real (tiny) interval
    assert b.batch.count == 1
    assert b.batch_average() < 0.05
    # reader side: after_reader with a stale anchor must not record either
    b.reset()
    b.after_reader()
    assert b.reader.count == 0


def test_profiler_export_honors_path(tmp_path):
    d = str(tmp_path / "trace")
    p = prof.Profiler(on_trace_ready=prof.export_chrome_tracing(d))
    p.start()
    jax.block_until_ready(jnp.ones((4, 4)) @ jnp.ones((4, 4)))
    p.step()
    p.stop()
    dest = str(tmp_path / "exported_copy")
    assert p.export(path=dest) == dest
    src_files = sorted(f for _, _, fs in os.walk(d) for f in fs)
    dst_files = sorted(f for _, _, fs in os.walk(dest) for f in fs)
    assert dst_files == src_files and dst_files
    with np.testing.assert_raises(ValueError):
        p.export(format="csv")


def test_profiler_export_without_trace_raises():
    p = prof.Profiler(timer_only=True)
    p.start()
    p.stop()
    with np.testing.assert_raises(RuntimeError):
        p.export(path="/tmp/nowhere")
    assert p.export() is None   # no-path form still returns the (absent) dir


def test_parse_trace_op_times_reports_skipped_files(tmp_path):
    """Unreadable trace files are counted and named in rows.meta, so an
    empty summary is distinguishable from a parse failure."""
    import gzip
    import json as _json

    run = tmp_path / "plugins" / "profile" / "run1"
    run.mkdir(parents=True)
    good = {"traceEvents": [
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "/host:CPU"}},
        {"ph": "X", "name": "my_op", "pid": 1, "dur": 5.0},
    ]}
    with gzip.open(run / "good.trace.json.gz", "wt") as f:
        _json.dump(good, f)
    (run / "corrupt.trace.json.gz").write_bytes(b"not gzip at all")
    dev, host = prof.parse_trace_op_times(str(tmp_path))
    assert host and host[0]["name"] == "my_op"
    for rows in (dev, host):
        assert rows.meta["files_seen"] == 2
        assert rows.meta["files_skipped"] == 1
        (skipped_path, err), = rows.meta["skipped"]
        assert skipped_path.endswith("corrupt.trace.json.gz") and err
