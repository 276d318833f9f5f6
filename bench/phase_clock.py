"""Compile time from JAX's own monitoring events.

Adapted from ``chip_smoke.PhaseClock``: lowering and backend-compile
events are summed (tracing is left out, because nested jits report
nested, overlapping trace events), and each backend compile is counted,
so a run can show that nothing compiled inside its measured window.
"""

from __future__ import annotations

import jax

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class CompileClock:
    """Listens from construction until :meth:`close`."""

    def __init__(self):
        self.compile_s = 0.0
        self.backend_compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name in COMPILE_EVENTS:
            self.compile_s += secs
        if name == COMPILE_EVENTS[-1]:
            self.backend_compiles += 1

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)
