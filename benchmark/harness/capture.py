"""The traced part of a ``--trace 1`` run.

The profiler runs for the last few seconds of the measured window and
nowhere else; the host-clock layer metrics of a traced run are read from
the part of the window before it. With ``--trace 0`` nothing here
touches the profiler and ``span`` costs one ``if``.
"""

import contextlib
import glob
import os
import shutil

import jax


class Capture:
    """``start()`` once ``due(now)``; ``stop()`` ends the profiler and
    returns the ``.xplane.pb``. ``bench.window`` spans the traced part."""

    def __init__(self, enabled: bool, out_dir: str, start_at: float):
        self.enabled, self.out_dir, self.start_at = enabled, out_dir, start_at
        self.active = False
        self.started_at = None      # loop clock, when the profiler came up
        self._window = None

    def span(self, name: str):
        if not self.active:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(name)

    def due(self, now: float) -> bool:
        return (self.enabled and self.started_at is None
                and now >= self.start_at)

    def start(self, clock):
        shutil.rmtree(self.out_dir, ignore_errors=True)
        os.makedirs(self.out_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # only our own spans and XLA's
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self.active = True
        self.started_at = clock()
        self._window = jax.profiler.TraceAnnotation("bench.window")
        self._window.__enter__()

    def stop(self):
        if not self.active:
            return None
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False
        found = sorted(glob.glob(os.path.join(
            self.out_dir, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None
