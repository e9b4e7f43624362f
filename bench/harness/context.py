"""What one run hands its metric readers.

A reader (``bench/metrics/<name>.py``) is a function ``read(run)`` that
returns a number, or ``None`` where the run gave it nothing to read; the
harness then leaves that metric out of the result line.
"""
from __future__ import annotations

import dataclasses
import importlib.util
from pathlib import Path
from typing import Dict, List, Optional

from harness import trace as T
from harness.timeline import Record

BENCH = Path(__file__).resolve().parents[1]

#: The engine's decode tick as the device trace names it
#: (``jit_<function>``).
TICK_PROGRAM = r"jit_tick\b"


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Traced:
    """The traced part of a ``--trace 1`` run."""
    events: List[T.Event]
    t0: float                     # host clock at the trace's window span
    t1: float
    counters_open: Dict
    counters_close: Dict
    snap_open: dict               # cache state at t0 and t1
    snap_close: dict

    def counter(self, key: str) -> float:
        return self.counters_close.get(key, 0) - self.counters_open.get(key, 0)


@dataclasses.dataclass
class Run:
    dims: dict                    # model and engine sizes
    peaks: dict                   # bench/peaks/<device kind>.json
    setup_s: float
    records: List[Record]
    open: float
    close: float
    counters_open: Dict
    counters_close: Dict
    traced: Optional[Traced] = None

    def counter(self, key: str) -> float:
        return self.counters_close.get(key, 0) - self.counters_open.get(key, 0)

    def module_seconds(self, pattern: str) -> float:
        return T.seconds_of(self.traced.events, T.MODULES_LINE, pattern)

    def module_count(self, pattern: str) -> int:
        ev = T.matching(self.traced.events, T.MODULES_LINE, pattern)
        planes = T.device_planes(self.traced.events) or [None]
        return round(len(ev) / len(planes))

    def kernel(self, name: str):
        return load_module("kernels", name)

    def matmul_params(self) -> float:
        """Parameters a token multiplies by, at the cut depth: every
        layer's projections and MLP, and the LM head."""
        m = self.dims
        per_layer = m["d"] * (m["hq"] * m["hd"]) * 2 \
            + 2 * m["d"] * m["hkv"] * m["hd"] + 3 * m["d"] * m["ff"]
        return m["L"] * per_layer + m["d"] * m["V"]
