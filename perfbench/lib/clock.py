"""Compile accounting from ``jax.monitoring`` events.

Copied from ``chip_smoke.py``'s ``CompileClock`` so that the yardstick does
not move with the program: seconds spent in backend compiles (a
persistent-cache hit counts only its retrieval), programs compiled,
persistent-cache hits, and entries written to the cache.
"""
from __future__ import annotations

import dataclasses

import jax


@dataclasses.dataclass(frozen=True)
class CompileCount:
    seconds: float
    programs: int
    hits: int
    written: int

    def __sub__(self, other: "CompileCount") -> "CompileCount":
        return CompileCount(self.seconds - other.seconds,
                            self.programs - other.programs,
                            self.hits - other.hits,
                            self.written - other.written)


class CompileClock:
    """Counts every backend compile of the process from its creation on."""

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0
        self.hits = 0
        self.written = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.written += 1

    def snapshot(self) -> CompileCount:
        return CompileCount(self.seconds, self.programs, self.hits,
                            self.written)
