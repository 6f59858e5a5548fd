"""What JAX compiles, counted from its own monitoring events.

A copy of ``chip_smoke.py``'s ``_CompileLog`` (listed in PERF.md's open
questions: one of the two should go). One event per program XLA builds or
loads from the persistent cache, so a program "compiled" inside the measured
window shows whether it came from the cache or not.
"""

from __future__ import annotations

import jax


class CompileLog:
    def __init__(self):
        self.seconds = 0.0
        self.programs = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1
