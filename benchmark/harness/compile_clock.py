"""Backend compiles, counted from ``jax.monitoring``. The listener is
copied from ``chip_smoke.CompileClock`` (the original, which also keeps
seconds and cache hits, stays there for the smoke)."""

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """One listener for the whole run; the difference of ``compiles``
    between two instants is the compiles between them, which is how
    compiles inside the measured window are counted."""

    def __init__(self):
        import jax.monitoring as mon
        self.compiles = 0
        mon.register_event_duration_secs_listener(self._duration)

    def _duration(self, name, dur, **kw):
        if name == _COMPILE_EVENT:
            self.compiles += 1
