"""Counts backend compilations through ``jax.monitoring``.

A load from the persistent compilation cache counts too: it still builds an
executable, and no such event may fall inside a measured window.
"""

from __future__ import annotations

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileLog:
    """The backend compilations JAX reports while the log is open."""

    def __init__(self):
        self.events: list[tuple[str, float]] = []

    def _on(self, event: str, secs: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.events.append((str(kw.get("fun_name")), secs))

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)

    @property
    def seconds(self) -> float:
        return sum(s for _, s in self.events)
