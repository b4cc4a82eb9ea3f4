"""From a JAX profiler trace to the numbers the per-layer metrics read.

The traced run marks its window and the harness's host spans with
``jax.profiler.TraceAnnotation``; the profiler puts them on the same clock as
the device's events. Of the trace this reads:

* each device plane (``/device:TPU:<n>``): its ``XLA Modules`` line, one
  event per execution of a compiled program, and its ``XLA Ops`` line, one
  event per operation;
* the host plane (``/host:CPU``): the window span and the harness spans.

Busy time is the union of the operations' intervals inside the window,
averaged over the device planes that ran any; idle is the rest of the
window. Each idle gap is named by the innermost harness span the host was in
at the gap's midpoint.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW_SPAN = "traced_window"
TOP = 10

Interval = Tuple[float, float]


@dataclass
class Module:
    name: str           # program name without the "(<fingerprint>)"
    start: float        # ns, on the trace's clock
    end: float


@dataclass
class TraceSummary:
    window: Interval                      # ns
    busy_ns: float                        # mean over the devices that ran
    devices: int
    modules: List[Module]                 # of the first device, in order
    spans: List[Tuple[str, float, float]]  # harness spans (name, start, end)
    op_self_ns: Dict[str, float] = field(default_factory=dict)
    gaps: List[Tuple[str, float]] = field(default_factory=list)   # (span, ns)

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def executions(self, program: str) -> List[Module]:
        return [m for m in self.modules if m.name == program]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def self_times(events: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Time of each operation less the operations nested inside it (a
    ``while`` loop's body runs inside the loop's own event)."""
    out: Dict[str, float] = {}
    stack: List[List] = []          # [end, name, duration, nested]

    def close(entry) -> None:
        end, name, dur, child = entry
        out[name] = out.get(name, 0.0) + dur - child

    for start, dur, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= start:
            close(stack.pop())
        if stack:
            stack[-1][3] += dur
        stack.append([start + dur, name, dur, 0.0])
    while stack:
        close(stack.pop())
    return out


def _op_name(name: str) -> str:
    return name[:120]


def _program(name: str) -> str:
    return name.split("(", 1)[0]


def _innermost(spans: List[Tuple[str, float, float]], t: float) -> str:
    best: Optional[Tuple[str, float, float]] = None
    for s in spans:
        if s[1] <= t < s[2] and (best is None or s[1] >= best[1]):
            best = s
    return best[0] if best else "outside_harness_spans"


def reduce(profile, span_names: Iterable[str]) -> TraceSummary:
    span_names = set(span_names)
    window: Optional[Interval] = None
    spans: List[Tuple[str, float, float]] = []
    for plane in profile.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == WINDOW_SPAN:
                    window = (e.start_ns, e.start_ns + e.duration_ns)
                elif e.name in span_names:
                    spans.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    if window is None:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
    lo, hi = window

    busy: List[float] = []
    modules: List[Module] = []
    ops: List[Tuple[float, float, str]] = []
    first_busy: List[Interval] = []
    for plane in sorted(profile.planes, key=lambda p: p.name):
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {line.name: line for line in plane.lines}
        dev_ops = [(e.start_ns, e.duration_ns, e.name)
                   for e in lines["XLA Ops"].events] if "XLA Ops" in lines else []
        if not dev_ops:
            continue
        merged = clip(union((s, s + d) for s, d, _ in dev_ops), lo, hi)
        busy.append(sum(b - a for a, b in merged))
        if len(busy) == 1:          # the first device that ran stands for all
            first_busy = merged
            ops = [o for o in dev_ops if lo <= o[0] < hi]
            mods = lines["XLA Modules"].events if "XLA Modules" in lines else ()
            modules = [Module(_program(e.name), e.start_ns,
                              e.start_ns + e.duration_ns)
                       for e in mods if lo <= e.start_ns < hi]
    if not busy:
        return TraceSummary(window, 0.0, 0, [], spans)

    op_self: Dict[str, float] = {}
    for name, ns in self_times(ops).items():
        key = _op_name(name)
        op_self[key] = op_self.get(key, 0.0) + ns

    gaps: List[Tuple[str, float]] = []
    prev = lo
    for a, b in first_busy + [(hi, hi)]:
        if a > prev:
            gaps.append((_innermost(spans, (prev + a) / 2), a - prev))
        prev = max(prev, b)
    return TraceSummary(window, sum(busy) / len(busy), len(busy), modules,
                        spans, op_self, gaps)


def breakdown(summary: TraceSummary) -> Dict[str, List]:
    """The ``breakdown`` of a result line: the device operations with the
    most self time, and the device's idle time summed by the harness span the
    host was in, each entry named with its number of gaps."""
    ops = sorted(summary.op_self_ns.items(), key=lambda kv: -kv[1])[:TOP]
    by_span: Dict[str, List[float]] = {}
    for name, ns in summary.gaps:
        by_span.setdefault(name, []).append(ns)
    gaps = sorted(by_span.items(), key=lambda kv: -sum(kv[1]))[:TOP]
    return {"device_ops": [[n, ns / 1e9] for n, ns in ops],
            "idle_gaps": [[f"{n} ({len(g)} gaps)", sum(g) / 1e9]
                          for n, g in gaps]}
