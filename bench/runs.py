"""What every driver hands back to the harness, and the count of
compilations that tells whether anything compiled inside the window."""

from __future__ import annotations

import dataclasses

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Counts XLA compilations (persistent-cache hits included) from
    JAX's monitoring events."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.n += 1


@dataclasses.dataclass
class Run:
    """One run's result: ``metrics`` (end to end), ``facts`` (what the
    per-layer readers need besides the trace) and ``check`` (what the
    output check needs once the program's state is gone)."""
    attempted: int
    failed: int
    metrics: dict
    facts: dict
    check: dict
