"""Spans at the layer boundaries, kept in memory (no JAX here).

One :class:`Tracer` per :class:`~repro.core.api.BoltSystem`
(``system.tracer``). A span records its name, its start and end on
``time.perf_counter``, the index of the span it was opened inside (the one
that caused it) and a small dict of attributes; counts that belong to a span
are its attributes. Records keep the order in which their spans opened, so a
parent always comes before its children, and their first three fields are
those of a ``(name, start, end)`` tuple: a reader of such tuples reads them
too.

The tracer is off by default. While it is off, :meth:`Tracer.span` hands
back one shared object that records nothing and reads no clock. Given an
``annotate`` factory (``jax.profiler.TraceAnnotation``), each span also
enters ``annotate(name)``, so a running profiler session records it on the
clock of the device's events. Spans nest on one thread: the system is
driven from one.
"""

from __future__ import annotations

from time import perf_counter
from typing import Any, Callable, Dict, List, NamedTuple, Optional

# every span the program records, in the order a served batch opens them
SPAN_NAMES = (
    "serve.batch",      # one batch that admitted requests (the root)
    "serve.poll",       # the request subscription's poll
    "serve.prefill",    # the prompt positions' dispatch
    "serve.decode",     # the generated positions' dispatch
    "serve.fetch",      # the one host sync: generated tokens to the host
    "serve.encode",     # response records built
    "serve.append",     # the response append (session API below)
    "serve.ack",        # the append receipt's wait
    "serve.commit",     # the request cursor's commit
    "log.append",       # AgileLog.append / append_batch
    "log.wait",         # AppendReceipt.wait
    "meta.propose",     # a command proposed to the metadata raft
    "store.put",        # an object store PUT, every backend
)


class SpanRecord(NamedTuple):
    name: str
    start: float
    end: float
    parent: Optional[int]      # index of the enclosing span's record
    attrs: Dict[str, Any]


class _Off:
    """The span handed out while the tracer is off."""

    __slots__ = ()
    on = False

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def set(self, **attrs) -> None:
        pass

    def lap(self, position: int) -> None:
        pass

    def drop(self) -> None:
        pass


OFF = _Off()


class Span:
    """A span being recorded. Its record is written when it closes."""

    __slots__ = ("_tracer", "_ann", "name", "attrs", "index", "parent",
                 "start", "_last", "_laps", "_lap_max", "_lap_at")
    on = True

    def __init__(self, tracer: "Tracer", name: str, attrs: Dict[str, Any]) -> None:
        self._tracer = tracer
        self._ann = None
        self.name = name
        self.attrs = attrs
        self._laps = 0

    def __enter__(self) -> "Span":
        t = self._tracer
        self.parent = t._open[-1] if t._open else None
        self.index = len(t.spans)
        t.spans.append(None)        # the slot, filled when the span closes
        t._open.append(self.index)
        if t._annotate is not None:
            self._ann = t._annotate(self.name)
            self._ann.__enter__()
        self.start = self._last = perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        end = perf_counter()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        t = self._tracer
        t._open.pop()
        if self.index is None:        # dropped
            return
        if self._laps:
            self.attrs.update(positions=self._laps, host_max_s=self._lap_max,
                              host_max_at=self._lap_at)
        t.spans[self.index] = SpanRecord(self.name, self.start, end,
                                         self.parent, self.attrs)

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def lap(self, position: int) -> None:
        """One iteration of a loop inside the span ended: its host time runs
        from the previous lap's end, or from the span's opening. The span
        keeps their count (``positions``) and the largest (``host_max_s``,
        at ``host_max_at``)."""
        now = perf_counter()
        dt = now - self._last
        self._last = now
        if not self._laps:
            self._lap_max, self._lap_at = -1.0, position
        self._laps += 1
        if dt > self._lap_max:
            self._lap_max, self._lap_at = dt, position

    def drop(self) -> None:
        """Forget this span and every span opened inside it so far."""
        del self._tracer.spans[self.index:]
        self.index = None


class Tracer:
    """The span recorder of one system: ``spans`` and the ``intervals`` it
    was on for."""

    def __init__(self) -> None:
        # one record per span, in the order they opened; None while open
        self.spans: List[Optional[SpanRecord]] = []
        # [on, off] of each stretch the tracer recorded (off None while on)
        self.intervals: List[List[Optional[float]]] = []
        self.on = False
        self._annotate: Optional[Callable[[str], Any]] = None
        self._open: List[int] = []
        self._following = False

    def span(self, name: str, **attrs):
        """A context manager recording ``name`` around its body; ``OFF``
        while the tracer is off."""
        if not self.on:
            return OFF
        return Span(self, name, attrs)

    def enable(self, annotate: Optional[Callable[[str], Any]] = None) -> None:
        if not self.on:
            self.intervals.append([perf_counter(), None])
        self.on = True
        self._annotate = annotate
        self._following = False

    def disable(self) -> None:
        if self.on:
            self.intervals[-1][1] = perf_counter()
        self.on = False
        self._annotate = None
        self._following = False

    def follow(self, active: bool,
               annotate: Optional[Callable[[str], Any]] = None) -> None:
        """Record while an outside session is ``active``: switch on when it
        starts, off again when it stops. A tracer switched on by
        :meth:`enable` stays on."""
        if active and not self.on:
            self.enable(annotate)
            self._following = True
        elif not active and self._following:
            self.disable()
