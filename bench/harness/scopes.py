"""Readings from the program's own spans and scopes: device time under a
named scope of the decode tick, and device idle time under a host span,
per tick of the traced window.

The names come from the program (``repro.serving.tracing``):

* a device scope reaches each operation's event through the ``scope``
  frontend attribute, which the event's name (the HLO instruction text)
  carries as ``frontend_attributes={scope="tick_core/advance/..."}``;
* a host span is a ``TraceAnnotation`` event on a host plane, on any
  thread (the serve loop's, or the executor's that waits for a result).

Every interval is clipped to the benchmark's ``bench.window`` span; scope
time is also clipped to the tick program's executions, so that other
programs running the same code (prefill commits) do not count.  Nested
operations (a ``while`` and its body) count once: the readings take the
union of intervals.  A program older than these names gives nothing to
read, and each reading is then ``None``.
"""
from __future__ import annotations

import re
from typing import Iterable, List, Optional, Tuple

from harness import trace as T
from harness.context import TICK_PROGRAM

Intervals = List[Tuple[float, float]]


def names():
    """The program's span and scope names (``repro.serving.tracing``), or
    ``None`` where the program has none."""
    try:
        from repro.serving import tracing
    except ImportError:
        return None
    return tracing


def scope_path(event: T.Event, attr: str) -> Optional[str]:
    """The scope path the operation ran under, or ``None``."""
    m = re.search(rf'\b{re.escape(attr)}="([^"]*)"', event.name)
    return m.group(1) if m else None


def overlap(a: Intervals, b: Intervals) -> float:
    """Length of the intersection of two merged, sorted interval lists."""
    out, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def clipped(intervals: Iterable[Tuple[float, float]], lo: float,
            hi: float) -> Intervals:
    return T.union((max(a, lo), min(b, hi)) for a, b in intervals
                   if b > lo and a < hi)


def idle(events: List[T.Event], plane: str, lo: float,
         hi: float) -> Intervals:
    """Intervals of ``[lo, hi)`` in which no operation ran on ``plane``."""
    edges = [lo] + [x for ab in T.busy_intervals(events, plane, lo, hi)
                    for x in ab] + [hi]
    return [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2)
            if edges[k + 1] > edges[k]]


def tick_count(events: List[T.Event]) -> int:
    """Executions of the tick program in the window, per device plane."""
    planes = T.device_planes(events) or [None]
    return round(len(T.matching(events, T.MODULES_LINE, TICK_PROGRAM))
                 / len(planes))


def scope_ms_per_tick(events: List[T.Event], scope: str) -> Optional[float]:
    """Milliseconds per tick in which some operation under ``scope`` ran
    on the device, averaged over the device planes."""
    tr = names()
    planes = T.device_planes(events)
    ticks = tick_count(events)
    if tr is None or not planes or ticks <= 0:
        return None
    lo, hi = T.window(events)
    total, scoped = 0.0, False
    for p in planes:
        ticks_iv = clipped(((e.start, e.end) for e in events
                            if e.plane == p and e.line == T.MODULES_LINE
                            and re.search(TICK_PROGRAM, e.name)), lo, hi)
        under = []
        for e in events:
            if e.plane != p or e.line != T.OPS_LINE:
                continue
            path = scope_path(e, tr.SCOPE_ATTR)
            if path is None:
                continue
            scoped = True
            if scope in path.split("/"):
                under.append((e.start, e.end))
        total += overlap(clipped(under, lo, hi), ticks_iv)
    if not scoped:
        return None
    return total / len(planes) / ticks / 1e6


def span_intervals(events: List[T.Event], span: str, lo: float,
                   hi: float) -> Intervals:
    """Where a host span named ``span`` was open, on any host thread."""
    return clipped(((e.start, e.end) for e in events
                    if not T.is_device(e.plane) and e.name == span), lo, hi)


def idle_ms_per_tick(events: List[T.Event], span: str) -> Optional[float]:
    """Milliseconds per tick in which the device was idle while the host
    span ``span`` was open, averaged over the device planes."""
    planes = T.device_planes(events)
    ticks = tick_count(events)
    if names() is None or not planes or ticks <= 0:
        return None
    lo, hi = T.window(events)
    open_ = span_intervals(events, span, lo, hi)
    if not open_:
        return None
    total = sum(overlap(idle(events, p, lo, hi), open_) for p in planes)
    return total / len(planes) / ticks / 1e6
