"""The traced run: a profiler trace of a short steady stretch, the
program's own spans beside it on the same clock, reduced to the
numbers the per-layer readers use.

The profiler's clock starts at the trace's start; the program's spans
(`ray_tpu.util.tracing`, collected through its ``set_sink``) carry
`time.time()`. One `jax.profiler.TraceAnnotation` anchor, emitted
beside one `time.time()` reading, ties the two together.
"""

from __future__ import annotations

import os
import shutil
import threading
import time

from benchmark.harness import trace_reduce

BEGIN, END, ANCHOR = "bench.window_begin", "bench.window_end", "bench.anchor"
# Seconds allowed for the profiler to start before the window opens.
START_AHEAD_S = 2.0


class Tracer:
    def __init__(self, ctx):
        self.dir = os.path.join(ctx.scratch_dir, "trace")
        self.rehearse = ctx.rehearse
        self.spans = []          # the program's, wall clock
        self.anchor_wall = None
        self._thread = None
        shutil.rmtree(self.dir, ignore_errors=True)

    # The window, from whoever drives it.

    def arm(self) -> None:
        """Turn the program's own spans on. Before the lead-in: the
        engine emits spans only for requests MADE while they are on,
        and a request served in the window was made seconds before."""
        from ray_tpu.core.config import GLOBAL_CONFIG
        from ray_tpu.util import tracing

        tracing.set_sink(self.spans.extend)
        GLOBAL_CONFIG.set("tracing_enabled", True)

    def begin(self) -> None:
        import jax
        from jax.profiler import ProfileOptions

        opts = ProfileOptions()
        opts.python_tracer_level = 0     # the Python tracer slows the host
        opts.host_tracer_level = 2       # TraceAnnotation events
        self.arm()
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(ANCHOR):
            self.anchor_wall = time.time()

    def mark(self, name: str) -> None:
        import jax

        with jax.profiler.TraceAnnotation(name):
            pass

    def end(self) -> None:
        import jax

        from ray_tpu.core.config import GLOBAL_CONFIG
        from ray_tpu.util import tracing

        self.mark(END)
        GLOBAL_CONFIG.set("tracing_enabled", False)
        jax.profiler.stop_trace()
        tracing.flush()
        tracing.set_sink(None)

    def start_at(self, t0: float, window_s: float) -> None:
        """Trace [t0, t0 + window_s) on `time.perf_counter`'s clock from
        a thread of its own (the caller is busy sending load)."""

        self.arm()

        def body():
            time.sleep(max(0.0, t0 - START_AHEAD_S - time.perf_counter()))
            self.begin()
            time.sleep(max(0.0, t0 - time.perf_counter()))
            self.mark(BEGIN)
            time.sleep(max(0.0, t0 + window_s - time.perf_counter()))
            self.end()

        self._thread = threading.Thread(target=body, name="bench-tracer")
        self._thread.start()

    # The result.

    def finish(self) -> dict:
        if self._thread is not None:
            self._thread.join()
        trace = trace_reduce.load_xplane(self.dir)
        if self.rehearse:
            trace = trace_reduce.cpu_stand_in(trace)
        shutil.rmtree(self.dir, ignore_errors=True)
        lo, hi = trace_reduce.window_of(trace, BEGIN, END)
        anchor = trace_reduce.host_events(trace, ANCHOR)
        spans = [(e[0], e[1], e[1] + e[2])
                 for e in trace_reduce.host_events(trace, "bench.span.")]
        if anchor and self.anchor_wall is not None:
            shift = anchor[0][1] - self.anchor_wall * 1e9
            spans += [(s["name"], s["start"] * 1e9 + shift,
                       s["end"] * 1e9 + shift)
                      for s in self.spans
                      if s["name"].startswith("engine.") and s.get("end")]
        reduced = trace_reduce.reduce(trace, lo, hi, spans)
        reduced["program_spans"] = len(self.spans)
        return reduced
