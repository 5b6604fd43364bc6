"""Compile seconds and persistent-cache hits of this process.

A copy of chip_smoke.py's `Clock` (it ran on the v5e in PR 23), kept here
because the yardstick may not import what a later PR may change. It
listens to `jax.monitoring`; `compiles` counts backend compiles, which is
what "compilations inside the window" is taken from (a load from the
persistent cache fires the same event, so a warm run counts its loads in
set-up and still has to show none in the window).
"""
from __future__ import annotations

from typing import NamedTuple


class Snapshot(NamedTuple):
    compile_s: float
    compiles: int
    hits: int
    misses: int

    def since(self, earlier: "Snapshot") -> "Snapshot":
        return Snapshot(*(a - b for a, b in zip(self, earlier)))


class Clock:
    def __init__(self):
        from jax import monitoring
        self.compile_s = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        # the XLA compile or its load from the persistent cache; tracing
        # and lowering nest (a jit inside a jit is timed twice), so they
        # are not counted
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> Snapshot:
        return Snapshot(self.compile_s, self.compiles, self.hits, self.misses)
