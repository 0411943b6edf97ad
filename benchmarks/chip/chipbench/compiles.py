"""Counts XLA compilations, persistent-cache hits and the seconds spent
tracing, lowering and compiling, through JAX's monitoring events."""
from __future__ import annotations

_JAXPR_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_TO_MLIR = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """`compiles` counts backend compilations (a persistent-cache load
    counts too: JAX reports it under the same event); `seconds` is the host
    time spent in tracing, lowering and compiling, which a warm-up leaves
    out of its serving time."""

    def __init__(self):
        self.compiles = 0
        self.cache_hits = 0
        self.seconds = 0.0

    def install(self) -> "CompileCounter":
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == _BACKEND_COMPILE:
            self.compiles += 1
        if event in (_JAXPR_TRACE, _TO_MLIR, _BACKEND_COMPILE):
            self.seconds += secs

    def _event(self, event: str, **_kw) -> None:
        if event == _CACHE_HIT:
            self.cache_hits += 1
