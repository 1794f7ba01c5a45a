"""A `jax.profiler` trace of a few seconds of the steady window."""

from __future__ import annotations

import os
import shutil
import tempfile
import threading
import time


class WindowTracer:
    """Starts the profiler `after_s` into the window and stops it `seconds`
    later. `tick` is called from the loop that drives the device (training),
    or `follow` runs it from a thread (serving). The trace goes under TMPDIR
    and is removed once reduced."""

    def __init__(self, after_s: float, seconds: float):
        self.after_s, self.seconds = after_s, seconds
        self.dir = tempfile.mkdtemp(prefix="perfbench_trace_")
        self.state = 0
        self.t_on = self.t_off = None
        self._thread = None

    def _start(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # the Python tracer floods the host
        opts.host_tracer_level = 2       # TraceAnnotation spans stay
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t_on = time.perf_counter()
        self.state = 1

    def _stop(self):
        import jax
        self.t_off = time.perf_counter()
        jax.profiler.stop_trace()
        self.state = 2

    def tick(self, elapsed: float, sync=None):
        if self.state == 0 and elapsed >= self.after_s:
            if sync:
                sync()
            self._start()
        elif self.state == 1 and elapsed >= self.after_s + self.seconds:
            if sync:
                sync()
            self._stop()

    def follow(self, t0: float):
        def loop():
            while self.state < 2:
                self.tick(time.perf_counter() - t0)
                time.sleep(0.02)
        self._thread = threading.Thread(target=loop, name="perfbench-trace",
                                        daemon=True)
        self._thread.start()

    def finish(self):
        if self._thread is not None:
            self._thread.join(self.after_s + self.seconds + 60.0)
        if self.state == 1:
            self._stop()

    def reduce(self):
        from . import trace
        try:
            if self.state != 2:
                return None
            return trace.reduce(trace.find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)

    def keep(self, dest: str):
        """Copy the raw .xplane.pb out (tools only)."""
        from . import trace
        os.makedirs(os.path.dirname(dest), exist_ok=True)
        shutil.copy(trace.find_xplane(self.dir), dest)
