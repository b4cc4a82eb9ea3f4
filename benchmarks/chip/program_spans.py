"""The program's own spans (``repro.core.tracing``), as the metric readers
see them.

The serve engine's tracer records while JAX's profiler runs, so in a traced
run it covers the traced stretch of the window, whole batches: the readers
read from the first recorded batch's start to the last one's end. A reader is
handed the run's record, which holds no handle on the system, so the spans
are taken from the tracers alive in the process. Every reader here clips to
the measured interval and to the stretches the tracer recorded; a program
without the tracer has none, and the readers then return None.
"""

from __future__ import annotations

import gc
import math
from typing import Dict, Iterator, List, Optional, Tuple

PHASES = ("serve.prefill", "serve.decode")   # the spans with per-position laps


def tracers() -> list:
    try:
        from repro.core.tracing import Tracer
    except ImportError:
        return []
    return [o for o in gc.get_objects() if isinstance(o, Tracer)]


def recorded(rec, tracer) -> List[Tuple[float, float]]:
    """The stretches of the measured interval that ``tracer`` recorded:
    in each of its on-intervals, from the start of the first ``serve.batch``
    to the end of the last. The engine switches the tracer off only at the
    next batch, so the on-interval itself also holds the profiler's stop."""
    lo, hi = rec.window
    out = []
    for a, b in tracer.intervals:
        b = math.inf if b is None else b
        served = [s for s in tracer.spans if s is not None
                  and s.name == "serve.batch" and a <= s.start < b]
        if served:
            x, y = max(served[0].start, lo), min(served[-1].end, hi)
            if y > x:
                out.append((x, y))
    return out


def runs(rec) -> Iterator[Tuple[list, List[Tuple[float, float]]]]:
    """(closed span records, recorded stretches) of each tracer that
    recorded inside the measured interval."""
    for t in tracers():
        stretches = recorded(rec, t)
        if stretches:
            yield [s for s in t.spans if s is not None], stretches


def share(rec, names) -> Optional[float]:
    """% of the recorded part of the measured interval inside the spans
    named ``names``."""
    inside = total = 0.0
    seen = False
    for spans, stretches in runs(rec):
        total += sum(b - a for a, b in stretches)
        for s in spans:
            if s.name in names:
                seen = True
                inside += sum(max(0.0, min(s.end, b) - max(s.start, a))
                              for a, b in stretches)
    return 100.0 * inside / total if seen and total > 0 else None


def batches(rec) -> Iterator[Tuple[object, Dict[str, list]]]:
    """Each ``serve.batch`` that started in a recorded stretch, with its
    descendants by name."""
    for spans, stretches in runs(rec):
        for i, s in enumerate(spans):
            if s.name != "serve.batch" or not any(a <= s.start < b
                                                  for a, b in stretches):
                continue
            under: Dict[str, list] = {}
            for c in spans[i + 1:]:
                if c.start >= s.end:
                    break
                under.setdefault(c.name, []).append(c)
            yield s, under
