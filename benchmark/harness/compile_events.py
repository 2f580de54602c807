"""Count XLA compilations, so one inside the measured window shows.

Listens to JAX's own monitoring events. Every program the backend is
asked for fires one ``backend_compile`` duration event, whether the
persistent cache serves it or not: a program that was not warmed stalls
the window either way.
"""

from __future__ import annotations

import threading

_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._count = 0  # guarded by: self._lock

    def install(self) -> "CompileCounter":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        return self

    def _on_duration(self, event: str, duration: float, **_: object) -> None:
        if event == _EVENT:
            with self._lock:
                self._count += 1

    def count(self) -> int:
        with self._lock:
            return self._count
