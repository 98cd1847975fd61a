"""Compilations of this process, counted from outside the program through
``jax.monitoring`` (a copy of ``chip_smoke.py::CompileMeter``, process-wide
only: the benchmark's controller thread reads what the scheduler's loop
thread compiled)."""

from __future__ import annotations

import threading


class CompileMeter:
    def __init__(self) -> None:
        import jax

        self._lock = threading.Lock()
        self.seconds = 0.0
        self.programs = 0       # backend compiles, cache hits included
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event.startswith("/jax/core/compile/"):
            with self._lock:
                self.seconds += secs
                if event.endswith("backend_compile_duration"):
                    self.programs += 1

    def _event(self, event: str, **_kw) -> None:
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

    def snapshot(self) -> dict:
        with self._lock:
            return dict(compile_s=round(self.seconds, 2),
                        programs=self.programs, cache_hits=self.cache_hits,
                        cache_misses=self.cache_misses)
