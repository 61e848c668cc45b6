"""Count XLA compilations from JAX's own monitoring events (the way
``chip_smoke.py`` does), so a run can say how many programs it compiled or
loaded, and that none of them fell inside the measured window."""

from __future__ import annotations

import threading


class CompileMeter:
    def __init__(self) -> None:
        import jax.monitoring as mon

        self._lock = threading.Lock()
        self.seconds = 0.0
        self.compiles = 0  # programs compiled or loaded from the cache
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.seconds += secs
                self.compiles += 1

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "compiles": self.compiles,
                "cache_hits": self.cache_hits,
                "seconds": self.seconds,
            }
