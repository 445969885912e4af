"""Counts of JAX's own compile events, taken from ``jax.monitoring``.

JAX records one ``jaxpr_to_mlir_module`` duration event for every
lowering of a traced program to MLIR, whether or not the compiled
program is then found in the persistent cache, and one
``backend_compile`` event for every compile or cache load.  Counting
them over the window says how often a call re-lowers what it could
have kept.
"""
from __future__ import annotations

import threading

LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
TRACE = "/jax/core/compile/jaxpr_trace_duration"


class CompileCounter:
    """Counts lowerings, backend compiles and traces while ``armed``.

    JAX keeps its listeners for the life of the process, so one counter
    is registered once and armed around the window."""

    def __init__(self):
        self._lock = threading.Lock()
        self.armed = False
        self.counts = {LOWERING: 0, BACKEND_COMPILE: 0, TRACE: 0}
        self.seconds = {LOWERING: 0.0, BACKEND_COMPILE: 0.0, TRACE: 0.0}

    def register(self) -> "CompileCounter":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, event: str, duration: float, **_kw) -> None:
        if self.armed and event in self.counts:
            with self._lock:
                self.counts[event] += 1
                self.seconds[event] += float(duration)

    def snapshot(self) -> dict:
        with self._lock:
            return {"lowerings": self.counts[LOWERING],
                    "backend_compiles": self.counts[BACKEND_COMPILE],
                    "traces": self.counts[TRACE],
                    "lowering_s": self.seconds[LOWERING],
                    "backend_compile_s": self.seconds[BACKEND_COMPILE],
                    "trace_s": self.seconds[TRACE]}
