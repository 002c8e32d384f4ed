"""Arithmetic the yardstick owns: percentiles and a compile counter.

Copied in (not imported) so that a change to the program cannot change how
the benchmark counts.  ``percentile`` is numpy's default (linear
interpolation between order statistics), as the program's own latency
summary uses; ``CompileClock`` is the serve smoke's compile clock, extended
to count persistent-cache loads as well as backend compiles.
"""
from __future__ import annotations

import math
import threading


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


class CompileClock:
    """Counts what JAX compiles or loads from its persistent cache, from its
    own monitoring events.  ``mark()`` starts a new span; ``since()`` says
    how many compiles and cache loads happened after the mark."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self._lock = threading.Lock()
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self._mark = (0, 0)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == self.COMPILE:
            with self._lock:
                self.compiles += 1
                self.compile_s += duration

    def _on_event(self, event, **_):
        if event == self.CACHE_HIT:
            with self._lock:
                self.cache_hits += 1

    def mark(self):
        with self._lock:
            self._mark = (self.compiles, self.cache_hits)

    def since(self) -> dict:
        with self._lock:
            return {"compiles": self.compiles - self._mark[0],
                    "cache_loads": self.cache_hits - self._mark[1]}
