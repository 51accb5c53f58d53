"""Compile counting, copied from the program's `chip_smoke.py` so that
the yardstick stays put when the program changes."""


class CompileClock:
    """Sums JAX's backend compile events.  A persistent-cache hit fires
    the event too (and takes far less), so ``count`` counts every program
    the process had not yet loaded."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.count += 1
