"""Compile activity, read from JAX's monitoring events.

Counts backend compiles (and their seconds), persistent-cache hits and
misses, and traces to a jaxpr.  ``mark()`` snapshots the counters so that
set-up and the measured window can each be charged with their own: a
program compiled or loaded from the cache inside the window is a compile
inside the window.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Marks:
    compile_s: float
    compiles: int
    hits: int
    misses: int
    traces: int

    def since(self, earlier: "Marks") -> "Marks":
        return Marks(*(a - b for a, b in zip(dataclasses.astuple(self),
                                             dataclasses.astuple(earlier))))

    @property
    def in_window(self) -> int:
        """Programs built or loaded: backend compiles plus cache hits."""
        return self.compiles + self.hits


class CompileWatch:
    def __init__(self):
        from jax import monitoring
        self.compile_s = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        self.traces = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration
            self.compiles += 1
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self) -> Marks:
        return Marks(self.compile_s, self.compiles, self.hits, self.misses,
                     self.traces)
