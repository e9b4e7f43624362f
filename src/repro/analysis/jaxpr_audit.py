"""Control-flow-aware jaxpr census — the static half of the compiled-path
contract auditor (see docs/analysis.md).

``census_of(jax.make_jaxpr(fn)(*args))`` walks a (closed) jaxpr through
every control-flow primitive — ``scan`` / ``while`` / ``cond`` / ``pjit``
/ ``shard_map`` / custom-derivative calls — and returns a :class:`Census`
of everything the compiled path stages:

* **pallas launches** as a linear form ``launches + trips *
  launches_per_trip`` (scan bodies multiplied by the static trip count,
  ``while`` bodies by the symbolic trip count), plus the un-multiplied
  launch *sites* with their jaxpr paths;
* **cond branch launch counts per branch** — the generalization of the
  old ``ops.count_pallas_launches``, which took ``max`` over branches and
  silently hid branch-count divergence; divergent branches are recorded
  so contracts can reject branch-dependent dispatch;
* **collectives** with primitive name, axis names, and operand dtype
  (reducing vs pure-data-movement), for the cross-shard bit-identity
  contract;
* **host callbacks** and **in-graph transfers** (``device_put`` /
  infeed/outfeed) — each one a host round-trip risk on the hot path;
* **float64 values** and widening float ``convert_element_type``
  upcasts (upcasts are informational; fp64 is contract-forbidden).

The walker is pure static analysis: nothing is executed, so auditing an
entry point is safe before any compile.  ``count_launches`` is the exact
legacy counting semantics (kept as the compatibility target of
``kernels.ops.count_pallas_launches``).
"""
from __future__ import annotations

import dataclasses
from collections import Counter
from typing import Dict, List, Tuple

import numpy as np
from jax.extend import core as jcore

#: Collectives that REDUCE values across shards — these change math when
#: the mesh changes unless the operand is integer (exact) or whitelisted.
REDUCING_COLLECTIVES = frozenset({"psum", "pmin", "pmax", "reduce_scatter"})

#: Collectives that only MOVE data across shards (no arithmetic): safe at
#: any dtype — gathering head shards is bit-exact concatenation.
MOVEMENT_COLLECTIVES = frozenset({"all_gather", "all_to_all", "ppermute",
                                  "pbroadcast", "pgather"})

#: Primitives that call back into the host — a synchronous device->host
#: round-trip when staged on the serving hot path.
CALLBACK_PRIMITIVES = frozenset({"pure_callback", "io_callback",
                                 "debug_callback", "callback",
                                 "outside_call"})

#: In-graph transfer primitives (explicit placement / host feeds).
TRANSFER_PRIMITIVES = frozenset({"device_put", "infeed", "outfeed"})


def _inner(jaxpr):
    """ClosedJaxpr | Jaxpr -> Jaxpr."""
    return jaxpr.jaxpr if isinstance(jaxpr, jcore.ClosedJaxpr) else jaxpr


def _subjaxprs(params):
    """Yield every sub-jaxpr stored in an eqn's params (generic fallback
    for pjit / shard_map / remat / custom_*_call / closed_call / ...)."""
    for v in params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for x in vals:
            if isinstance(x, jcore.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jcore.Jaxpr):
                yield x


def count_launches(jaxpr, while_trips: int = 1) -> int:
    """Static per-call ``pallas_call`` LAUNCH count of a (closed) jaxpr.

    Launches inside a ``lax.scan`` body are multiplied by the scan trip
    count; a ``lax.while_loop``'s body launches are multiplied by
    ``while_trips`` (nested whiles multiply — the count is evaluated, not
    a closed form) and its cond launches counted once.  ``cond`` branches
    contribute their MAXIMUM — callers that care about branch-count
    divergence must use :func:`census_of`, which records per-branch
    counts (this max is exactly the legacy
    ``kernels.ops.count_pallas_launches`` behaviour, kept for the
    compatibility shim and as the worst-case bound).
    """
    jaxpr = _inner(jaxpr)
    n = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "pallas_call":
            n += 1
        elif name == "scan":
            n += eqn.params["length"] * count_launches(
                eqn.params["jaxpr"], while_trips)
        elif name == "cond":
            n += max(count_launches(b, while_trips)
                     for b in eqn.params["branches"])
        elif name == "while":
            n += while_trips * count_launches(
                eqn.params["body_jaxpr"], while_trips)
            n += count_launches(eqn.params["cond_jaxpr"], while_trips)
        else:
            n += sum(count_launches(j, while_trips)
                     for j in _subjaxprs(eqn.params))
    return n


@dataclasses.dataclass(frozen=True)
class PrimitiveUse:
    """One occurrence of a primitive of interest, with its jaxpr path."""
    name: str
    path: str
    detail: str = ""

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class CollectiveUse:
    """One collective eqn: name, mesh axes, operand dtype, reduce-ness."""
    name: str
    axis_names: Tuple[str, ...]
    dtype: str
    reduces: bool
    path: str

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class CondBranches:
    """Per-branch static launch counts of one ``cond`` (at one while
    trip).  Recorded only for conds where at least one branch stages a
    launch — all-zero conds (data-dependent math, no dispatch) are
    uninteresting."""
    path: str
    branches: Tuple[int, ...]

    @property
    def divergent(self) -> bool:
        return len(set(self.branches)) > 1

    def to_dict(self) -> dict:
        return {"path": self.path, "branches": list(self.branches),
                "divergent": self.divergent}


@dataclasses.dataclass
class Census:
    """Everything one compiled entry point stages, per call."""
    launches: int = 0             # launches OUTSIDE while bodies
    launches_per_trip: int = 0    # launches per while trip
    nonlinear: bool = False       # nested whiles stage launches
    launch_sites: List[str] = dataclasses.field(default_factory=list)
    cond_launches: List[CondBranches] = dataclasses.field(
        default_factory=list)
    collectives: List[CollectiveUse] = dataclasses.field(
        default_factory=list)
    callbacks: List[PrimitiveUse] = dataclasses.field(default_factory=list)
    transfers: List[PrimitiveUse] = dataclasses.field(default_factory=list)
    fp64: List[PrimitiveUse] = dataclasses.field(default_factory=list)
    upcasts: List[PrimitiveUse] = dataclasses.field(default_factory=list)
    prim_counts: Counter = dataclasses.field(default_factory=Counter)

    def launches_at(self, while_trips: int = 1) -> int:
        """Total launches assuming every while loop runs ``while_trips``
        trips.  Exact for linear (non-nested-while) programs; for the
        rare nested case callers should re-count via
        :func:`count_launches` (``nonlinear`` is set)."""
        return self.launches + while_trips * self.launches_per_trip

    def to_dict(self) -> dict:
        return {
            "launches": self.launches,
            "launches_per_trip": self.launches_per_trip,
            "nonlinear": self.nonlinear,
            "launch_sites": list(self.launch_sites),
            "cond_launches": [c.to_dict() for c in self.cond_launches],
            "collectives": [c.to_dict() for c in self.collectives],
            "callbacks": [c.to_dict() for c in self.callbacks],
            "transfers": [c.to_dict() for c in self.transfers],
            "fp64": [c.to_dict() for c in self.fp64],
            "upcasts": [c.to_dict() for c in self.upcasts],
            "prim_counts": dict(self.prim_counts),
        }


def _axis_names(eqn) -> Tuple[str, ...]:
    axes = eqn.params.get("axes")
    if axes is None:
        axes = eqn.params.get("axis_name", ())
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    return tuple(str(a) for a in axes)


def _check_dtypes(eqn, name: str, path: str, census: Census) -> None:
    for v in eqn.outvars:
        dt = getattr(getattr(v, "aval", None), "dtype", None)
        if dt is not None and str(dt) in ("float64", "complex128"):
            census.fp64.append(PrimitiveUse(name, path, f"-> {dt}"))
            break
    if name == "convert_element_type":
        old = getattr(eqn.invars[0].aval, "dtype", None)
        new = eqn.params.get("new_dtype")
        if (old is not None and new is not None
                and np.issubdtype(old, np.floating)
                and np.issubdtype(new, np.floating)
                and np.dtype(new).itemsize > np.dtype(old).itemsize):
            census.upcasts.append(PrimitiveUse(name, path, f"{old}->{new}"))


def _walk(jaxpr, census: Census, path: str) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        here = f"{path}/{name}" if path else name
        census.prim_counts[name] += 1
        _check_dtypes(eqn, name, here, census)
        if name == "pallas_call":
            # the kernel body is device-internal: launch accounting stops
            # here (count_launches matches), but don't descend for the
            # host-facing checks either — a kernel can't call back out.
            kernel = eqn.params.get("name")
            census.launch_sites.append(f"{here}({kernel})" if kernel
                                       else here)
            continue
        if name in CALLBACK_PRIMITIVES:
            cb = eqn.params.get("callback")
            census.callbacks.append(PrimitiveUse(
                name, here, getattr(cb, "__name__", "") if cb else ""))
            continue
        if name in TRANSFER_PRIMITIVES:
            census.transfers.append(PrimitiveUse(name, here))
            continue
        if name in REDUCING_COLLECTIVES or name in MOVEMENT_COLLECTIVES:
            dt = str(eqn.invars[0].aval.dtype) if eqn.invars else "?"
            census.collectives.append(CollectiveUse(
                name=name, axis_names=_axis_names(eqn), dtype=dt,
                reduces=name in REDUCING_COLLECTIVES, path=here))
            continue
        if name == "cond":
            branches = eqn.params["branches"]
            counts = tuple(count_launches(b) for b in branches)
            if any(counts):
                census.cond_launches.append(CondBranches(here, counts))
            for i, b in enumerate(branches):
                _walk(_inner(b), census, f"{here}[br{i}]")
            continue
        if name == "scan":
            _walk(_inner(eqn.params["jaxpr"]), census, f"{here}[body]")
            continue
        if name == "while":
            _walk(_inner(eqn.params["cond_jaxpr"]), census,
                  f"{here}[cond]")
            _walk(_inner(eqn.params["body_jaxpr"]), census,
                  f"{here}[body]")
            continue
        # generic recursion: pjit / shard_map / remat / custom_*_call ...
        label = eqn.params.get("name")
        sub = f"{here}({label})" if isinstance(label, str) else here
        for j in _subjaxprs(eqn.params):
            _walk(j, census, sub)


def census_of(jaxpr) -> Census:
    """Build the full :class:`Census` of a (closed) jaxpr.

    The launch linear form is derived from :func:`count_launches` at
    while-trip counts 1/2/3 — ``per_trip = at(2) - at(1)``, with
    ``nonlinear`` flagged when ``at(3) - at(2)`` disagrees (launches in
    nested while loops; no engine entry point does this, and contracts
    reject it).
    """
    census = Census()
    inner = _inner(jaxpr)
    _walk(inner, census, "")
    c1 = count_launches(inner, while_trips=1)
    c2 = count_launches(inner, while_trips=2)
    c3 = count_launches(inner, while_trips=3)
    census.launches_per_trip = c2 - c1
    census.launches = c1 - census.launches_per_trip
    census.nonlinear = (c3 - c2) != census.launches_per_trip
    if isinstance(jaxpr, jcore.ClosedJaxpr):
        for const in jaxpr.consts:
            if str(getattr(const, "dtype", "")) == "float64":
                census.fp64.append(PrimitiveUse(
                    "const", "consts",
                    f"float64 constant shape {getattr(const, 'shape', ())}"
                ))
    return census
