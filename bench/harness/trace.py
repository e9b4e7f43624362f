"""Reduction of a profiler trace to device busy time, kernel and program
times, and idle gaps by host span.

A trace is read once into flat :class:`Event` tuples (plane, line, name,
start, duration, in nanoseconds on the trace's one clock), so the
reduction can be checked on a small recorded trace with no profiler.

* Device planes are named ``/device:<KIND>:<n>``; their ``XLA Ops`` line
  holds one event per operation that ran, their ``XLA Modules`` line one
  per program execution.
* Host planes are named ``/host:...``; the benchmark's own
  ``TraceAnnotation`` spans appear on their thread lines under the span's
  name.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, Iterable, List, NamedTuple, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start: float        # ns
    dur: float          # ns
    detail: str = ""    # the event's stats that name what it ran

    @property
    def end(self) -> float:
        return self.start + self.dur


def load_xplane(path: str) -> List[Event]:
    """Every event of an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                detail = ""
                if plane.name.startswith("/device:"):
                    detail = " ".join(str(v) for k, v in e.stats
                                      if k in ("long_name", "tf_op",
                                               "hlo_op", "hlo_module",
                                               "kernel_details"))
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns),
                                 detail))
    return out


def is_device(plane: str) -> bool:
    return plane.startswith("/device:")


def device_planes(events: Iterable[Event]) -> List[str]:
    return sorted({e.plane for e in events
                   if is_device(e.plane) and e.line == OPS_LINE})


def window(events: Iterable[Event]) -> Tuple[float, float]:
    """The traced window: the benchmark's ``bench.window`` host span."""
    spans = [e for e in events if e.name == WINDOW_SPAN
             and not is_device(e.plane)]
    if not spans:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    return min(e.start for e in spans), max(e.end for e in spans)


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float,
                                                                  float]]:
    """Merged, sorted, disjoint intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def busy_intervals(events: Iterable[Event], plane: str, lo: float,
                   hi: float):
    return union(_clip([(e.start, e.end) for e in events
                        if e.plane == plane and e.line == OPS_LINE],
                       lo, hi))


def busy_seconds(events: List[Event]) -> float:
    """Seconds in the window in which some operation ran, averaged over
    the device planes."""
    lo, hi = window(events)
    planes = device_planes(events)
    if not planes:
        return 0.0
    return sum(sum(b - a for a, b in busy_intervals(events, p, lo, hi))
               for p in planes) / len(planes) / 1e9


def _in_window(events, line, lo, hi):
    return [e for e in events if is_device(e.plane) and e.line == line
            and e.start >= lo and e.start < hi]


def matching(events: List[Event], line: str, pattern: str) -> List[Event]:
    """Device events of ``line`` inside the window whose name or detail
    matches the regular expression ``pattern``."""
    lo, hi = window(events)
    rx = re.compile(pattern)
    return [e for e in _in_window(events, line, lo, hi)
            if rx.search(e.name) or rx.search(e.detail)]


def seconds_of(events: List[Event], line: str, pattern: str) -> float:
    """Summed device seconds of the matching events, averaged over the
    device planes."""
    planes = device_planes(events) or [None]
    return sum(e.dur for e in matching(events, line, pattern)) \
        / len(planes) / 1e9


def op_name(name: str) -> str:
    """An operation event's HLO instruction name (its event name is the
    whole instruction text)."""
    return name.split(" = ", 1)[0].lstrip("%")


def top_ops(events: List[Event], n: int = 10) -> List[list]:
    """The device operations (HLO instructions) that took most time in
    the window."""
    lo, hi = window(events)
    planes = device_planes(events) or [None]
    tot: Dict[str, float] = defaultdict(float)
    for e in _in_window(events, OPS_LINE, lo, hi):
        tot[op_name(e.name)] += e.dur
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / len(planes) / 1e9] for k, v in ranked]


def idle_gaps_by_span(events: List[Event], spans: Iterable[str],
                      n: int = 10) -> List[list]:
    """Idle device time in the window, summed by the host span that was
    open at each instant (the innermost of ``spans``; ``host.other`` where
    none was)."""
    spans = set(spans)
    lo, hi = window(events)
    host = sorted(((e.start, e.end, e.name) for e in events
                   if not is_device(e.plane) and e.name in spans),
                  key=lambda t: t[0])
    planes = device_planes(events)
    tot: Dict[str, float] = defaultdict(float)
    for p in planes:
        busy = busy_intervals(events, p, lo, hi)
        edges = [lo] + [x for ab in busy for x in ab] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        for a, b in gaps:
            covered = []
            for s, e, name in host:
                if s >= b:
                    break
                if e > a:
                    covered.append((max(s, a), min(e, b), e - s, name))
            cuts = sorted({a, b} | {x for c in covered for x in c[:2]})
            for x, y in zip(cuts, cuts[1:]):
                open_ = [c for c in covered if c[0] <= x and c[1] >= y]
                name = min(open_, key=lambda c: c[2])[3] if open_ \
                    else "host.other"
                tot[name] += y - x
    scale = max(len(planes), 1) * 1e9
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / scale] for k, v in ranked]
