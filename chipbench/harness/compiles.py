"""Count XLA executables built (compiled, or read from the persistent
cache) and the seconds spent on them, from JAX's monitoring events."""
from __future__ import annotations

import jax

EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    def __init__(self):
        self.count, self.secs = 0, 0.0
        self.names = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, fun_name: str = "?", **_):
        if event == EVENT:
            self.count += 1
            self.secs += secs
            self.names.append(fun_name)
