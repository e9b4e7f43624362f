"""Declarative contracts over compiled entry points (docs/analysis.md).

A :class:`CompiledContract` pins what one compiled path is ALLOWED to
stage — exact pallas launch counts (fixed + per while trip), no host
callbacks, no in-graph transfers, no float64, no cond branches with
divergent launch counts, and a :class:`CollectiveRule` bounding
cross-shard communication.  ``audit_engine(engine)`` audits every entry
point the engine registers (``ThinKVEngine.compiled_entry_points``)
against ``engine_contracts(engine)`` and returns an
:class:`AuditReport`; a registered entry point with no declared contract
is itself an error — new compiled paths must declare their contract.

``audit_serve_step`` / ``audit_train_step`` / ``audit_flash_prefill``
extend the same checks to the non-engine compiled paths (the dryrun
steps and the standalone prefill kernel).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.analysis.jaxpr_audit import Census, census_of

_MAX_ITEMIZED = 5      # cap per-item violations so reports stay readable


@dataclasses.dataclass(frozen=True)
class Violation:
    """One broken contract rule, with the offending jaxpr path."""
    contract: str
    rule: str            # launch-count | launch-per-trip | ...
    message: str
    path: str = ""

    def __str__(self) -> str:
        loc = f" at {self.path}" if self.path else ""
        return f"[{self.contract}] {self.rule}: {self.message}{loc}"

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class CollectiveRule:
    """What cross-shard communication a compiled path may stage.

    ``movement`` collectives (pure data movement, e.g. the tiled
    attention-head ``all_gather``) are allowed at any dtype — they are
    bit-exact concatenation.  ``integer_reductions`` (e.g. the COW
    dirty-mask ``psum`` OR) are allowed on integer/bool operands only —
    integer arithmetic is exact regardless of reduction order.  Any
    float reduction must appear in ``float_reductions`` as a
    ``(primitive, axis)`` pair; the serving engine whitelists NONE
    (bit-identity across mesh sizes, the PR 5 gate)."""
    movement: Tuple[str, ...] = ("all_gather",)
    integer_reductions: Tuple[str, ...] = ("psum",)
    float_reductions: Tuple[Tuple[str, str], ...] = ()

    def check(self, contract: str, collectives) -> List["Violation"]:
        out = []
        for c in collectives:
            if not c.reduces:
                if c.name in self.movement:
                    continue
                out.append(Violation(
                    contract, "collective",
                    f"{c.name}({c.dtype}) over axes {list(c.axis_names)} "
                    f"is not a whitelisted movement collective "
                    f"(allowed: {list(self.movement)})", c.path))
                continue
            is_float = np.issubdtype(np.dtype(c.dtype), np.floating)
            if not is_float and c.name in self.integer_reductions:
                continue
            if is_float and any(c.name == p and a in c.axis_names
                                for p, a in self.float_reductions):
                continue
            out.append(Violation(
                contract, "collective",
                f"reduction {c.name}({c.dtype}) over axes "
                f"{list(c.axis_names)} crosses shards — the bit-identity "
                f"contract allows integer {list(self.integer_reductions)} "
                f"and movement {list(self.movement)} only", c.path))
        return out


@dataclasses.dataclass(frozen=True)
class CompiledContract:
    """The declared invariants of ONE compiled entry point."""
    name: str
    launches: int = 0             # exact launches outside while bodies
    launches_per_trip: int = 0    # exact launches per while trip
    forbid_callbacks: bool = True
    forbid_transfers: bool = True
    forbid_fp64: bool = True
    forbid_branch_divergence: bool = True
    #: None = collectives unchecked (e.g. sharded train_step, which
    #: legitimately all-reduces grads); a rule = every collective must
    #: satisfy it.
    collectives: Optional[CollectiveRule] = None
    note: str = ""

    def check(self, census: Census) -> List[Violation]:
        v: List[Violation] = []
        if census.launches != self.launches:
            v.append(Violation(
                self.name, "launch-count",
                f"{census.launches} pallas launch(es) staged outside "
                f"loop bodies, contract pins {self.launches}; launch "
                f"sites: {census.launch_sites or '(none)'}"))
        if census.launches_per_trip != self.launches_per_trip:
            v.append(Violation(
                self.name, "launch-per-trip",
                f"{census.launches_per_trip} pallas launch(es) per while "
                f"trip, contract pins {self.launches_per_trip}; launch "
                f"sites: {census.launch_sites or '(none)'}"))
        if census.nonlinear:
            v.append(Violation(
                self.name, "nonlinear-launches",
                "launch count is not linear in the while trip count "
                "(launches staged inside nested while loops)"))
        if self.forbid_branch_divergence:
            for cb in census.cond_launches:
                if cb.divergent:
                    v.append(Violation(
                        self.name, "branch-divergence",
                        f"cond branches stage {list(cb.branches)} "
                        f"launches — branch-dependent dispatch (the old "
                        f"max-over-branches count hid this)", cb.path))
        for flag, items, rule, what in (
                (self.forbid_callbacks, census.callbacks, "callback",
                 "host callback"),
                (self.forbid_transfers, census.transfers, "transfer",
                 "in-graph transfer"),
                (self.forbid_fp64, census.fp64, "fp64",
                 "float64 value")):
            if not flag:
                continue
            for it in items[:_MAX_ITEMIZED]:
                v.append(Violation(
                    self.name, rule,
                    f"{what} {it.name} {it.detail}".rstrip(), it.path))
            if len(items) > _MAX_ITEMIZED:
                v.append(Violation(
                    self.name, rule,
                    f"... and {len(items) - _MAX_ITEMIZED} more"))
        if self.collectives is not None:
            v.extend(self.collectives.check(self.name, census.collectives))
        return v

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["collectives"] = (dataclasses.asdict(self.collectives)
                            if self.collectives is not None else None)
        return d


class ContractViolation(AssertionError):
    """Raised by ``AuditReport.raise_on_violation`` — message lists every
    broken rule with its jaxpr path."""


@dataclasses.dataclass
class EntryAudit:
    """census + contract + violations for one entry point."""
    name: str
    census: Census
    contract: CompiledContract
    violations: List[Violation]

    @property
    def ok(self) -> bool:
        return not self.violations

    def to_dict(self) -> dict:
        return {"name": self.name, "ok": self.ok,
                "census": self.census.to_dict(),
                "contract": self.contract.to_dict(),
                "violations": [v.to_dict() for v in self.violations]}


@dataclasses.dataclass
class AuditReport:
    """All entry-point audits of one engine/config cell."""
    entries: Dict[str, EntryAudit]
    meta: Dict = dataclasses.field(default_factory=dict)

    @property
    def ok(self) -> bool:
        return all(e.ok for e in self.entries.values())

    @property
    def violations(self) -> List[Violation]:
        return [v for e in self.entries.values() for v in e.violations]

    def raise_on_violation(self) -> "AuditReport":
        if not self.ok:
            lines = "\n".join(f"  {v}" for v in self.violations)
            raise ContractViolation(
                f"compiled-path contract audit failed "
                f"({len(self.violations)} violation(s)):\n{lines}")
        return self

    def summary(self) -> str:
        lines = []
        for name, e in sorted(self.entries.items()):
            c = e.census
            status = "OK " if e.ok else "FAIL"
            lines.append(
                f"[{status}] {name}: launches={c.launches}"
                f"+{c.launches_per_trip}/trip "
                f"collectives={len(c.collectives)} "
                f"callbacks={len(c.callbacks)} fp64={len(c.fp64)}")
            lines.extend(f"       {v}" for v in e.violations)
        return "\n".join(lines)

    def to_dict(self) -> dict:
        return {"ok": self.ok, "meta": dict(self.meta),
                "entries": {k: e.to_dict()
                            for k, e in sorted(self.entries.items())}}


def serve_collective_rule() -> CollectiveRule:
    """The serving engine's collective whitelist, sourced from the
    sharding scheme (``distributed.sharding.serve_collective_whitelist``)
    so the contract and the mesh layout live together."""
    from repro.distributed.sharding import serve_collective_whitelist
    w = serve_collective_whitelist()
    return CollectiveRule(
        movement=tuple(w["movement"]),
        integer_reductions=tuple(w["integer_reductions"]),
        float_reductions=tuple(w["float_reductions"]))


def engine_contracts(engine) -> Dict[str, CompiledContract]:
    """The declared contract of every ``ThinKVEngine`` compiled entry
    point.  Kernel backend: the decode tick is ONE fused launch (layer
    axis folded into the grid), the mega-dispatch is one launch per
    while TRIP and none outside, chunked prefill is one paged launch per
    layer, and the big-chunk path adds one ``flash_prefill`` launch per
    layer.  Reference backend: zero launches everywhere.  All entry
    points share the serve collective whitelist, no callbacks, no
    transfers, no fp64."""
    L = engine.dims.L
    k = engine.backend == "kernel"
    rule = serve_collective_rule()
    cons = {
        "_tick_fn": CompiledContract(
            "_tick_fn", launches=1 if k else 0, collectives=rule,
            note="decode tick: one fused ct_paged_attention launch"),
        "_prefill_chunk_fn": CompiledContract(
            "_prefill_chunk_fn", launches=L if k else 0, collectives=rule,
            note="g-chunk prefill: one paged launch per layer (the "
                 "intra-chunk flash part runs the jnp oracle)"),
        "_megatick_fn": CompiledContract(
            "_megatick_fn", launches=0,
            launches_per_trip=1 if k else 0, collectives=rule,
            note="mega-dispatch: one fused launch per TRIP, zero "
                 "outside the while loop"),
        "_prefill_big_fn": CompiledContract(
            "_prefill_big_fn", launches=2 * L if k else 0,
            collectives=rule,
            note="big-chunk prefill: paged + flash_prefill launch per "
                 "layer"),
        # declared unconditionally; only audited when the engine was
        # built with drift_probe=True and registered the entry point
        "_drift_probe_fn": CompiledContract(
            "_drift_probe_fn", launches=0, collectives=rule,
            note="drift probe: dense teacher-forced replay, plain jit "
                 "(replicated, off the tick hot path) — no kernel "
                 "launches on either backend"),
    }
    return cons


def audit_engine(engine,
                 contracts: Optional[Dict[str, CompiledContract]] = None,
                 ) -> AuditReport:
    """Audit every registered engine entry point against its contract.

    Raises ``KeyError`` if an entry point has no declared contract —
    registering a new compiled path in ``compiled_entry_points`` without
    declaring its invariants is exactly the regression this subsystem
    exists to catch."""
    import jax

    eps = engine.compiled_entry_points()
    cons = dict(engine_contracts(engine))
    if contracts:
        cons.update(contracts)
    entries = {}
    for name, (fn, args) in eps.items():
        if name not in cons:
            raise KeyError(
                f"no CompiledContract declared for engine entry point "
                f"{name!r} — add one to analysis.contracts."
                f"engine_contracts (see docs/analysis.md)")
        census = census_of(jax.make_jaxpr(fn)(*args))
        entries[name] = EntryAudit(name, census, cons[name],
                                   cons[name].check(census))
    meta = {
        "backend": engine.backend,
        "layers": int(engine.dims.L),
        "devices": int(engine.mesh.devices.size)
        if engine.mesh is not None else 1,
        "ticks_per_dispatch": int(engine.ticks_per_dispatch),
        "max_seqs": int(engine.cfg.max_seqs),
    }
    return AuditReport(entries=entries, meta=meta)


def audit_flash_prefill(seq: int = 128, heads: int = 4, kv_heads: int = 2,
                        head_dim: int = 16) -> EntryAudit:
    """Contract audit of the standalone ``flash_prefill`` kernel: exactly
    one launch, nothing host-facing.  Compiled on a TPU, interpret mode
    elsewhere."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.flash_prefill import flash_prefill

    interpret = jax.default_backend() != "tpu"

    def fn(q, kk, vv):
        return flash_prefill(q, kk, vv, interpret=interpret)

    q = jax.ShapeDtypeStruct((seq, heads, head_dim), jnp.float32)
    kv = jax.ShapeDtypeStruct((seq, kv_heads, head_dim), jnp.float32)
    census = census_of(jax.make_jaxpr(fn)(q, kv, kv))
    con = CompiledContract("flash_prefill", launches=1,
                           collectives=CollectiveRule(),
                           note="standalone prefill kernel: one launch")
    return EntryAudit("flash_prefill", census, con, con.check(census))


def _model_step_audits(arch: str = "r1-llama-8b") -> Dict[str, EntryAudit]:
    """Contract audits of the non-engine compiled steps (the dryrun
    seam): smoke-config ``serve_step`` prefill/decode and ``train_step``.
    On CPU these run the jnp oracles, so zero launches; the binding
    contract is no fp64, no callbacks, no in-graph transfers.
    Collectives are unchecked — sharded training legitimately
    all-reduces gradients."""
    import jax
    import jax.numpy as jnp

    from repro.config import OptimizerConfig, ThinKVConfig
    from repro.configs import get_smoke_config
    from repro.models import build_model
    from repro.serving import serve_step as SS
    from repro.training.optimizer import adamw_init
    from repro.training.train_step import make_train_step

    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init_params(seed=0)
    B, S = 2, 16

    out: Dict[str, EntryAudit] = {}

    def _audit(name, fn, *args, launches=0):
        census = census_of(jax.make_jaxpr(fn)(*args))
        con = CompiledContract(name, launches=launches, collectives=None,
                               note="dryrun-seam step (CPU oracle path)")
        out[name] = EntryAudit(name, census, con, con.check(census))

    tokens = jax.ShapeDtypeStruct((B, S), jnp.int32)
    _audit("prefill_step", SS.make_prefill_step(model, cfg),
           params, {"tokens": tokens})

    budget = 64
    from repro.config import InputShape
    from repro.models import input_specs
    decode = SS.make_decode_step_thinkv(cfg, ThinKVConfig(
        token_budget=budget))
    shape = InputShape("audit_decode", budget, B, "decode")
    batch = input_specs(cfg, shape, thinkv_budget=budget)
    _audit("decode_step_thinkv", decode, params, batch)

    step = make_train_step(model.loss, cfg, OptimizerConfig())
    opt = jax.eval_shape(adamw_init, params)
    tbatch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
              "targets": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    _audit("train_step", step, params, opt, tbatch)
    return out
