"""In-program spans: one tracer for the serve loop, the page store, the store
cluster and the simulated NVM.

``span(name, **attrs)`` is a context manager placed at each layer boundary.

- Off (the default), it returns one shared no-op context and records
  nothing: a flag test and a return.
- On (``enable()`` until ``disable()``), each span records an ``Event``
  (id, name, start, end, parent id, attrs) on ``time.perf_counter`` into a
  fixed-size in-memory ring, and opens a ``jax.profiler.TraceAnnotation``
  named ``PREFIX + name``, so that a profiler trace taken meanwhile holds the
  span on its host plane, on the device trace's clock.  ``parent`` is the
  span open around it (spans nest; one thread).  ``attrs`` holds ``seq_id``
  (one per serving session) and ``nbytes`` where bytes move.

``events(lo, hi)`` returns the recorded spans that began in ``[lo, hi)`` and
the number of spans the ring has dropped since ``enable()``;
``self_seconds(events)`` each span's duration less its children's.

The tracer writes nothing out and reads no option: a caller enables it,
and reads what it recorded.  jax is imported only by ``enable()``, so the
modules that place spans stay importable without it.
"""
from __future__ import annotations

import time
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

#: the name of every span in a profiler trace starts with this
PREFIX = "repro:"

#: spans the ring holds; past this the oldest are overwritten and counted
#: as dropped
CAPACITY = 1 << 16


class Event(NamedTuple):
    id: int                 # order in which the span was opened
    name: str
    t0: float               # time.perf_counter() at entry
    t1: float               # ... and at exit
    parent: Optional[int]   # id of the enclosing span, or None
    attrs: Dict


class _Off:
    """The shared context ``span`` returns while the tracer is off."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass


_OFF = _Off()


class _Span:
    __slots__ = ("tracer", "name", "attrs", "id", "parent", "t0", "note")

    def __init__(self, tracer: "Tracer", name: str, attrs: Dict):
        self.tracer, self.name, self.attrs = tracer, name, attrs

    def __enter__(self):
        tr = self.tracer
        self.id = tr.opened
        tr.opened += 1
        self.parent = tr.stack[-1] if tr.stack else None
        tr.stack.append(self.id)
        self.note = tr.annotation(PREFIX + self.name)
        self.note.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.note.__exit__(*exc)
        tr = self.tracer
        tr.stack.pop()
        tr.record(Event(self.id, self.name, self.t0, t1, self.parent,
                        self.attrs))
        return False

    def set(self, **attrs) -> None:
        """Add attributes known only inside the span (bytes read, say)."""
        self.attrs.update(attrs)


class Tracer:
    """The ring and the stack of open spans.  The module keeps one."""

    def __init__(self):
        self.capacity = CAPACITY
        self.on = False
        self.ring: List[Optional[Event]] = []
        self.recorded = 0   # spans recorded since enable()
        self.opened = 0     # span ids handed out
        self.stack: List[int] = []
        self.annotation = None

    def enable(self) -> None:
        """Empty the ring and record from now on."""
        from jax.profiler import TraceAnnotation
        self.annotation = TraceAnnotation
        self.ring = [None] * self.capacity
        self.recorded = 0
        self.on = True

    def disable(self) -> None:
        """Record nothing more; what was recorded stays readable."""
        self.on = False

    def record(self, event: Event) -> None:
        self.ring[self.recorded % self.capacity] = event
        self.recorded += 1

    @property
    def dropped(self) -> int:
        return max(0, self.recorded - self.capacity)

    def events(self, lo: float = float("-inf"),
               hi: float = float("inf")) -> Tuple[List[Event], int]:
        kept = min(self.recorded, self.capacity)
        first = self.recorded - kept
        held = [self.ring[i % self.capacity]
                for i in range(first, self.recorded)]
        return ([e for e in held if lo <= e.t0 < hi], self.dropped)


TRACER = Tracer()


def span(name: str, **attrs):
    """A span around the block (see the module docstring); off, the shared
    no-op context."""
    if not TRACER.on:
        return _OFF
    return _Span(TRACER, name, attrs)


def enable() -> None:
    TRACER.enable()


def disable() -> None:
    TRACER.disable()


def events(lo: float = float("-inf"),
           hi: float = float("inf")) -> Tuple[List[Event], int]:
    """(the recorded spans that began in [lo, hi), in the order they
    closed; the number of spans dropped since ``enable()``)."""
    return TRACER.events(lo, hi)


def self_seconds(evs: Sequence[Event]) -> Dict[int, float]:
    """Each span's duration less the union of its children's (by id).  A
    child missing from ``evs`` is not subtracted."""
    children: Dict[int, List[Tuple[float, float]]] = {}
    for e in evs:
        if e.parent is not None:
            children.setdefault(e.parent, []).append((e.t0, e.t1))
    out = {}
    for e in evs:
        covered, end = 0.0, float("-inf")
        for a, b in sorted(children.get(e.id, ())):
            a = max(a, end)
            if b > a:
                covered += b - a
                end = b
        out[e.id] = (e.t1 - e.t0) - covered
    return out
