"""Reduction of a profiler trace to device metrics.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but JAX's
``ProfileData``.  Device planes are ``/device:TPU:<n>``; their ``XLA Ops``
line gives the operations (busy union, top operations) and their
``XLA Modules`` line the program executions.  The harness's host spans are
``TraceAnnotation`` events named ``bench:<label>`` on the host plane, on the
same clock as the device's to within about a millisecond.

- busy: the union of operation intervals inside the window, per chip,
  averaged over chips.
- programs by label: each program execution is credited to the harness span
  most recently begun by its start plus ``skew_ns`` (host and device clocks
  disagree by up to about a millisecond), or to ``host`` when none was.
- idle gaps: the stretches of the window in which no operation ran, each
  labelled by the harness spans open at its midpoint, outermost first
  (``snapshot/multi_write``), or ``host`` if none; summed per label.
"""
from __future__ import annotations

import bisect
import collections
import re
from typing import Dict, List, Optional, Sequence, Tuple

SPAN_PREFIX = "bench:"
WINDOW_SPAN = SPAN_PREFIX + "window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
Interval = Tuple[float, float]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge intervals into disjoint, sorted ones."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The stretches of [lo, hi] that ``busy`` (disjoint, sorted) leaves."""
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, min(a, hi)))
        at = max(at, b)
    if at < hi:
        out.append((at, hi))
    return [(a, b) for a, b in out if b > a]


class Spans:
    """Harness spans, sorted by start, for 'which span is open at t' and
    'which span was most recently begun by t'."""

    #: how many spans back a containing span is looked for: spans nest a few
    #: deep, and a span holds few others
    DEPTH = 64

    def __init__(self, spans: Sequence[Tuple[float, float, str]]):
        self.spans = sorted(spans)
        self.starts = [s[0] for s in self.spans]

    def begun_by(self, t: float) -> Optional[str]:
        i = bisect.bisect_right(self.starts, t) - 1
        return self.spans[i][2] if i >= 0 else None

    def open_at(self, t: float) -> Optional[str]:
        """The spans containing t, outermost first, joined by '/' (as
        'snapshot/multi_write'), or None."""
        i = bisect.bisect_right(self.starts, t) - 1
        names = [self.spans[j][2] for j in range(max(i - self.DEPTH + 1, 0),
                                                 i + 1)
                 if self.spans[j][1] >= t]
        return "/".join(names) or None


def read_events(profile) -> Dict:
    """Pull what the reduction needs out of a ``ProfileData``: per device,
    its operation and program intervals; and the harness's host spans."""
    devices, spans = {}, []
    for plane in profile.planes:
        if _DEVICE_PLANE.match(plane.name):
            ops, progs = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(e.start_ns, e.end_ns, e.name) for e in line.events]
                elif line.name == "XLA Modules":
                    progs = [(e.start_ns, e.end_ns, e.name)
                             for e in line.events]
            devices[plane.name] = {"ops": ops, "programs": progs}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.start_ns, e.end_ns, e.name[len(SPAN_PREFIX):])
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return {"devices": devices, "spans": spans}


def leaf_ops(ops: Sequence[Tuple[float, float, str]]):
    """The operations that contain no other: a ``while`` op and the fusions
    of its body lie on one line, and only the body's time is the work."""
    ops = sorted(ops, key=lambda e: (e[0], -e[1]))
    return [e for i, e in enumerate(ops)
            if i + 1 == len(ops) or ops[i + 1][0] >= e[1]]


def _op_name(name: str) -> str:
    """'%fusion.113 = bf16[...] fusion(...)' -> 'fusion.113'."""
    head = name.split(" = ", 1)[0]
    return head.lstrip("%") or name


def reduce(events: Dict, *, skew_ns: float = 2e6, top: int = 10) -> Dict:
    """Device metrics of a traced window (see the module docstring).

    Returns {window_s, busy_s, program_s: {label: seconds}, device_ops:
    [[name, seconds]], idle_gaps: [[label, seconds]]}; seconds are means
    over the chips.  The window is the
    ``window`` span, or the extent of the device events where there is none.
    """
    devices = events["devices"]
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    window = [s for s in events["spans"] if s[2] == "window"]
    harness = Spans([s for s in events["spans"] if s[2] != "window"])
    if window:
        lo, hi = window[0][0], window[0][1]
    else:
        lo = min(e[0] for d in devices.values() for e in d["ops"])
        hi = max(e[1] for d in devices.values() for e in d["ops"])
    n = len(devices)
    busy_ns = 0.0
    prog_ns: Dict[str, float] = collections.defaultdict(float)
    op_ns: Dict[str, float] = collections.defaultdict(float)
    idle_ns: Dict[str, float] = collections.defaultdict(float)
    for dev in devices.values():
        ops = [(a, b, nm) for a, b, nm in dev["ops"] if b > lo and a < hi]
        busy = union(clip([(a, b) for a, b, _ in ops], lo, hi))
        busy_ns += sum(b - a for a, b in busy)
        for a, b in gaps(busy, lo, hi):
            idle_ns[harness.open_at((a + b) / 2) or "host"] += b - a
        for a, b, nm in leaf_ops(ops):
            op_ns[_op_name(nm)] += min(b, hi) - max(a, lo)
        for a, b, _ in dev["programs"]:
            if lo <= a < hi:
                label = harness.begun_by(a + skew_ns) or "host"
                prog_ns[label] += b - a

    def ranked(d):
        return [[k, v / n / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"window_s": (hi - lo) / 1e9, "busy_s": busy_ns / n / 1e9,
            "program_s": {k: v / n / 1e9 for k, v in prog_ns.items()},
            "device_ops": ranked(op_ns), "idle_gaps": ranked(idle_ns)}


def reduce_file(path: str, **kw) -> Dict:
    from jax.profiler import ProfileData
    return reduce(read_events(ProfileData.from_file(path)), **kw)
