"""Sums jax's own compile-time events (``jax.monitoring``), so that set-up
can say how much of it was backend compilation and a window can prove that
nothing compiled inside it. Copied from ``chip_smoke.py`` (PR 21)."""

from __future__ import annotations


class CompileClock:
    def __init__(self) -> None:
        import jax.monitoring

        self.backend_s = 0.0
        self.trace_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.backend_s += secs
            self.compiles += 1
        elif event.endswith(("jaxpr_trace_duration",
                             "jaxpr_to_mlir_module_duration")):
            self.trace_s += secs

    def _event(self, event: str, **_kw) -> None:
        if event.endswith("/cache_hits"):
            self.cache_hits += 1
        elif event.endswith("/cache_misses"):
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.backend_s, "trace_lower_s": self.trace_s,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses, "compiles": self.compiles}
