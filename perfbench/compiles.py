"""Backend compiles counted through JAX's monitoring events."""
from __future__ import annotations


class CompileCounter:
    """Counts backend compiles (each new executable, persistent-cache hit or
    not) and their seconds."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.n, self.secs = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == self.EVENT:
            self.n += 1
            self.secs += duration
