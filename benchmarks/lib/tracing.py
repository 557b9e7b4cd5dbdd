"""Starting and stopping the jax profiler for the traced sub-window, and
counting compilations inside a window."""

import contextlib
import os
import shutil

import jax


class SubWindowTrace:
    """The profiler, on for a sub-window only.  ``start``/``stop`` are
    called from the benchmark's wrappers around the program's calls; the
    python tracer is off (it slows the host and fills the trace)."""

    def __init__(self, logdir: str):
        self.logdir = logdir
        self.on = False
        self.done = False

    def start(self):
        if self.on or self.done:
            return
        shutil.rmtree(self.logdir, ignore_errors=True)
        os.makedirs(self.logdir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.logdir, profiler_options=opts)
        self.on = True

    def stop(self):
        if not self.on:
            return
        jax.profiler.stop_trace()
        self.on, self.done = False, True


def annotate(name: str):
    """A host span in the profiler's own trace (``bench.<name>``); costs a
    few hundred nanoseconds while no trace is on."""
    return jax.profiler.TraceAnnotation("bench." + name)


class CompileCounter:
    """Counts backend compilations while ``counting()`` is entered."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self._active = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        if self._active and event == self.EVENT:
            self.count += 1

    @contextlib.contextmanager
    def counting(self):
        self._active = True
        try:
            yield self
        finally:
            self._active = False
