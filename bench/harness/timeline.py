"""What the clients saw, and the arithmetic on it.

Every time here is a host ``time.perf_counter()`` reading.  A request's
latency counts from when it was DUE on the schedule, not from when the
generator got round to submitting it, so a stall that delays submissions
still shows.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Record:
    """One request as its client saw it."""
    index: int
    due: float                       # scheduled submit time
    prompt_len: int
    max_new: int
    submit: Optional[float] = None   # when the client submitted it
    stamps: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    finished: bool = False           # the stream ended with every token
    ended: Optional[float] = None    # when the stream ended, for any reason
    stats: dict = dataclasses.field(default_factory=dict)  # engine's, at finish
    # the engine's own list of the tokens it produced, read after the run
    served: List[int] = dataclasses.field(default_factory=list)


def p95(values) -> Optional[float]:
    v = np.asarray(list(values), float)
    return float(np.percentile(v, 95)) if v.size else None


def delivered(recs: List[Record], t0: float, t1: float) -> int:
    """Tokens delivered inside ``[t0, t1)``."""
    return sum(1 for r in recs for s in r.stamps if t0 <= s < t1)


def token_rate(recs: List[Record], t0: float, t1: float) -> float:
    return delivered(recs, t0, t1) / (t1 - t0)


def token_gaps(recs: List[Record], t0: float, t1: float) -> List[float]:
    """Every gap between consecutive tokens of one request whose later
    token lands inside ``[t0, t1)``."""
    out = []
    for r in recs:
        s = r.stamps
        out.extend(s[i] - s[i - 1] for i in range(1, len(s))
                   if t0 <= s[i] < t1)
    return out


def due_in(recs: List[Record], t0: float, t1: float) -> List[Record]:
    return [r for r in recs if t0 <= r.due < t1]


def first_token_waits(recs: List[Record], t0: float, t1: float):
    """First-token time minus due time of every request due in the
    window; one with no token by ``t1`` counts as ``t1``."""
    return [(r.stamps[0] if r.stamps and r.stamps[0] < t1 else t1) - r.due
            for r in due_in(recs, t0, t1)]


def lateness(recs: List[Record]) -> dict:
    """How late the generator submitted, over every submitted request."""
    late = np.array([r.submit - r.due for r in recs if r.submit is not None])
    if not late.size:
        return {"n": 0}
    return {"n": int(late.size), "p50_ms": float(np.median(late) * 1e3),
            "p95_ms": float(np.percentile(late, 95) * 1e3),
            "max_ms": float(late.max() * 1e3)}
