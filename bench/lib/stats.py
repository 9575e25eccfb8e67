"""Compile and persistent-cache events counted while a phase runs.

A copy of ``PhaseStats`` from ``chip_smoke.py``, kept with the benchmark:
JAX's backend-compile durations and compilation-cache hit/miss events,
read through ``jax.monitoring`` listeners.
"""
from __future__ import annotations

import collections

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileStats:
    """Counts backend compiles (and their seconds) and cache hits/misses
    from construction until ``close()``."""

    def __init__(self):
        self.compile_s = 0.0
        self.compiles = 0
        self.events = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.compile_s += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event.startswith("/jax/compilation_cache/cache_"):
            self.events[event.rsplit("/", 1)[1]] += 1

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "cache_hits": self.events["cache_hits"],
                "cache_misses": self.events["cache_misses"]}

    def close(self) -> dict:
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)
        return self.snapshot()
