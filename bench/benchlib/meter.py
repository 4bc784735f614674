"""Compile time from JAX's monitoring events.

``/jax/core/compile/backend_compile_duration`` fires once per XLA backend
compile; a program served from the persistent compilation cache does not
fire it.  The meter keeps the seconds and the count, so the harness can
split them into set-up and window.
"""
from __future__ import annotations

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileMeter:
    def __init__(self):
        self.seconds = 0.0
        self.count = 0

    def install(self, jax) -> "CompileMeter":
        jax.monitoring.register_event_duration_secs_listener(self.on_event)
        return self

    def on_event(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.seconds += duration
            self.count += 1

    def mark(self) -> tuple:
        return self.seconds, self.count
