"""Count XLA compilations through jax.monitoring (the listener pattern of
chip_smoke.child_traced): a measured window must see none."""

from __future__ import annotations


class CompileCounter:
    """Installed once per process; ``snapshot()`` before and after a
    window, and the difference is what compiled inside it."""

    def __init__(self) -> None:
        import jax

        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self.backend_compile_s = 0.0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.backend_compile_s += duration

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses,
                "backend_compile_s": round(self.backend_compile_s, 3)}
