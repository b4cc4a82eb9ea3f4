"""Backend compiles and persistent-cache loads, from JAX's monitoring events.

Copied from ``chip_smoke.py``'s ``CompileLog``, with the host-clock start of
each event kept so that the compiles inside a window can be counted. JAX
reports ``backend_compile_duration`` once a compile (or a load from the
persistent cache) has finished; its start is that moment less its duration.
"""

from __future__ import annotations

import time
from typing import List, Tuple

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileLog:
    def __init__(self) -> None:
        self.events: List[Tuple[str, float, float]] = []   # (fun, start, seconds)
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **kw) -> None:
        if event == COMPILE_EVENT:
            self.events.append((kw.get("fun_name"),
                                time.perf_counter() - seconds, seconds))

    def _event(self, event, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def started_in(self, lo: float, hi: float) -> List[Tuple[str, float, float]]:
        return [e for e in self.events if lo <= e[1] < hi]
