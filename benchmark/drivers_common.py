"""What the drivers share: the profiler around a window."""
from __future__ import annotations

import os
import shutil


class Tracing:
    """The profiler around the window, with the program's spans forwarded
    into it and the window itself marked on the profiler's clock."""

    def __init__(self, ctx):
        self.ctx = ctx
        self.mark = None

    def start(self):
        import jax

        from paddle_tpu.telemetry import tracing

        shutil.rmtree(self.ctx.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.ctx.trace_dir, profiler_options=opts)
        tracing.set_device_trace_active(True)
        self.mark = jax.profiler.TraceAnnotation("bench.window")
        self.mark.__enter__()

    def stop(self):
        import jax

        from benchmark.lib import trace as trace_mod
        from paddle_tpu.telemetry import tracing

        self.mark.__exit__(None, None, None)
        tracing.set_device_trace_active(False)
        jax.profiler.stop_trace()
        path = trace_mod.find_xplane(self.ctx.trace_dir)
        tr = trace_mod.load_xplane(path)
        if os.environ.get("BENCH_KEEP_TRACE"):
            # debugging aid: every plane and line with its largest events,
            # to look at a trace by hand (see benchmark/README.md)
            trace_mod.dump_summary(path, os.environ["BENCH_KEEP_TRACE"])
        shutil.rmtree(self.ctx.trace_dir, ignore_errors=True)
        return tr
