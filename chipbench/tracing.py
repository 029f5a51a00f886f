"""Profile the first stretch of a window.

The profiler runs from ``start`` for at most ``seconds`` (or until
``stop``), inside a host annotation ``bench.traced`` that
``tracereduce.reduce`` takes as the traced window.  A short stretch keeps
the trace small enough to read within the run's time limit.
"""
from __future__ import annotations

import shutil
import threading

import jax
from jax.profiler import TraceAnnotation

WINDOW = "bench.traced"


class Traced:
    def __init__(self, trace_dir: str, seconds: float):
        self.dir, self.seconds = trace_dir, seconds
        self._done = threading.Event()
        self._thread = None

    def start(self):
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._thread = threading.Thread(target=self._run, name="bench-trace")
        self._thread.start()

    def _run(self):
        with TraceAnnotation(WINDOW):
            self._done.wait(self.seconds)
        jax.profiler.stop_trace()

    def stop(self):
        self._done.set()
        self._thread.join()
