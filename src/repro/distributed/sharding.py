"""GSPMD sharding rules (DESIGN.md Sec. 5).

Strategy:
* parameters — FSDP over the ``data`` axis (+``pod`` when present) on the
  d_model/reduction dim x tensor-parallel over ``model`` on the
  heads/d_ff/experts/vocab dim (ZeRO-3 + TP, MaxText-style);
* train batches — data-parallel over (``pod``, ``data``);
* decode KV caches / CT pools — the sequence/slot axis shards over ``model``
  (GQA kv_heads < |model| makes head sharding impossible; sequence-sharded
  caches + GSPMD softmax-stat psum is the scalable alternative);
* every rule is divisibility-checked; non-divisible dims fall back to
  replication (never a compile failure).

Rules are name-based over the param pytree paths, applied AFTER skipping the
leading stacked-layer axis.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config import ModelConfig

# pytree path substrings marking stacked-per-layer parameter groups
_STACKED_MARKERS = ("layers", "encoder", "decoder")


def _axis_sizes(mesh: Mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def fsdp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return (("pod", "data") if "pod" in mesh.axis_names else ("data",))


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return fsdp_axes(mesh)


def _fits(dim: int, mesh: Mesh, axes) -> bool:
    if axes is None:
        return True
    size = 1
    sizes = _axis_sizes(mesh)
    for a in (axes if isinstance(axes, tuple) else (axes,)):
        size *= sizes[a]
    return dim % size == 0 and dim >= size


def _spec_for(path: str, shape: Tuple[int, ...], mesh: Mesh,
              stacked: bool) -> P:
    fsdp = fsdp_axes(mesh)
    dims = list(shape[1:]) if stacked else list(shape)

    def build(*axes):
        """divisibility-checked spec over ``dims``; None-pad to rank."""
        out = []
        for dim, ax in zip(dims, list(axes) + [None] * (len(dims) - len(axes))):
            out.append(ax if _fits(dim, mesh, ax) else None)
        return P(*( [None] if stacked else [] ), *out)

    name = path.lower()
    if len(dims) == 0:
        return P()
    if len(dims) == 1:
        return build(None)

    # --- embeddings: [V, D] vocab on model, d on fsdp
    if "embedding" in name:
        return build("model", fsdp)
    if "lm_head" in name:
        return build(fsdp, "model")
    if "enc_pos" in name or "dec_pos" in name:
        return build(None, fsdp)

    # --- MoE experts [E, D, F]: EP over model when divisible, else TP on F
    if any(k in name for k in ("w_up", "w_gate")) and len(dims) == 3:
        if _fits(dims[0], mesh, "model"):
            return build("model", fsdp, None)
        return build(None, fsdp, "model")
    if "w_down" in name and len(dims) == 3:
        if _fits(dims[0], mesh, "model"):
            return build("model", None, fsdp)
        return build(None, "model", fsdp)
    if "router" in name:
        return build(fsdp, None)

    # --- attention
    if "wq" in name or "wk" in name or "wv" in name:
        return build(fsdp, "model")
    if "wo" in name:
        return build("model", fsdp)

    # --- dense mlp [D, F] / [F, D]
    if "w_up" in name or "w_gate" in name:
        return build(fsdp, "model")
    if "w_down" in name:
        return build("model", fsdp)

    # --- mamba: TP over d_inner
    if "in_proj" in name:
        return build(fsdp, "model")
    if "out_proj" in name:
        return build("model", fsdp)
    if "conv_w" in name:
        return build("model", None)
    if "x_proj" in name:
        return build("model", None)
    if "dt_proj" in name:
        return build(None, "model")
    if "a_log" in name:
        return build("model", None)

    # default: FSDP the first dim
    return build(fsdp)


def param_specs(params, mesh: Mesh, *, mode: str = "train"):
    """Pytree of PartitionSpec matching ``params``.

    mode="train": FSDP(data) x TP(model) — weight gathers amortize over
    thousands of tokens/device.
    mode="serve": TP(model) only, replicated over data — a decode step
    processes ONE token per request, so FSDP would re-gather every weight
    for every token (measured 10x+ memory-term inflation, EXPERIMENTS.md
    §Perf iteration 1); weights stay resident, sharded 16-way.
    """

    def one(path, leaf):
        pstr = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        stacked = any(m in pstr for m in _STACKED_MARKERS) and leaf.ndim >= 1
        spec = _spec_for(pstr, leaf.shape, mesh, stacked)
        if mode == "serve":
            drop = set(fsdp_axes(mesh))
            spec = P(*(None if (ax in drop or (isinstance(ax, tuple)
                                               and set(ax) & drop)) else ax
                       for ax in spec))
        return spec

    return jax.tree_util.tree_map_with_path(one, params)


def param_shardings(params, mesh: Mesh, *, mode: str = "train"):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_specs(params, mesh, mode=mode))


# ---------------------------------------------------------------------------
# batch / state specs
# ---------------------------------------------------------------------------

def train_batch_specs(batch, mesh: Mesh):
    """tokens/targets [B,S] -> P(dp, None); frontend feats likewise."""
    dp = dp_axes(mesh)

    def one(leaf):
        if leaf.ndim == 0:
            return P()
        if _fits(leaf.shape[0], mesh, dp):
            return P(dp, *([None] * (leaf.ndim - 1)))
        return P(*([None] * leaf.ndim))

    return jax.tree.map(one, batch)


def decode_batch_specs(batch, mesh: Mesh):
    """Decode-state sharding: batch over dp when divisible; cache/pool
    sequence axes over ``model`` (and over dp too when batch cannot shard —
    the long_500k single-request cell)."""
    dp = dp_axes(mesh)

    # names whose axis 2 is the sequence/slot axis ([B, L, T/NS, ...])
    seq_axis2 = ("k_cache", "v_cache", "k_codes", "v_codes", "k_scales",
                 "v_scales", "slot_state", "slot_bits", "cross_k", "cross_v")

    def one(path, leaf):
        name = "/".join(str(getattr(k, "key", k)) for k in path).lower()
        spec = [None] * leaf.ndim
        batch_sharded = leaf.ndim >= 1 and _fits(leaf.shape[0], mesh, dp)
        if batch_sharded:
            spec[0] = dp
        if any(s in name for s in seq_axis2) and leaf.ndim >= 3:
            seq_ax = ("model",) if batch_sharded else (dp + ("model",)) \
                if _fits(leaf.shape[2], mesh, dp + ("model",)) else ("model",)
            if _fits(leaf.shape[2], mesh, seq_ax):
                spec[2] = seq_ax if len(seq_ax) > 1 else seq_ax[0]
        return P(*spec)

    return jax.tree_util.tree_map_with_path(one, batch)


def to_shardings(specs, mesh: Mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s) if isinstance(s, P) else s, specs,
        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# serving-engine specs: tensor-parallel sharding of the ThinKV global pool
# ---------------------------------------------------------------------------
# The serving engine shards on the KV-HEAD axis of the paged planes
# ([L, NP, H, BS, ...] — axis 2) via shard_map: attention is embarrassingly
# parallel over heads, so per-shard math is bit-identical to a slice of the
# single-device run and only the attention OUTPUT rejoins the replicated
# residual stream (all-gather, pure data movement).  Everything head-
# agnostic — block tables, refcounts, slot/segment metadata, scheduler and
# prefix-cache state — stays REPLICATED, which keeps every admission/
# preemption/COW decision a replicated computation and the pool accounting
# shard-consistent by construction.  (This deliberately differs from
# ``decode_batch_specs``' sequence sharding of the FullKV path: the CT
# pool's slot axis is addressed by data-dependent scatters at every commit,
# while GQA serving configs keep kv_heads % |model| == 0.)

SERVE_HEAD_AXIS = "model"          # mesh axis the KV-head dim shards over
_PLANE_HEAD_DIM = 2                # [L, NP, H, BS, ...]
_BUF_HEAD_DIM = 2                  # per-request TBQ buffer [L, G, H, D]


def serve_plane_spec() -> P:
    """Pool / per-request paged planes ``[L, nb, H, BS, ...]``."""
    return P(*([None] * _PLANE_HEAD_DIM), SERVE_HEAD_AXIS)


def serve_buf_spec(batched: bool) -> P:
    """TBQ buffer spec: ``[L, G, H, D]`` (or ``[R, L, G, H, D]``)."""
    head = _BUF_HEAD_DIM + (1 if batched else 0)
    return P(*([None] * head), SERVE_HEAD_AXIS)


def serve_pool_specs(pool):
    """GlobalPool pytree of PartitionSpec: planes head-sharded, refcount
    replicated."""
    return type(pool)(
        view=type(pool.view)(*(serve_plane_spec() for _ in pool.view)),
        refcount=P())


def serve_cache_specs(cache, batched: bool):
    """CTCache pytree of PartitionSpec: TBQ buffer planes head-sharded,
    all metadata replicated.  ``batched`` selects the engine's stacked
    ``[R, ...]`` layout vs a single request's."""
    spec = {f: P() for f in cache.FIELDS}
    spec["buf_k"] = spec["buf_v"] = serve_buf_spec(batched)
    return type(cache)(**spec)


def head_shardable(num_kv_heads: int, mesh: Mesh) -> bool:
    """Can the serving engine shard ``num_kv_heads`` over mesh['model']?"""
    n = _axis_sizes(mesh).get(SERVE_HEAD_AXIS, 1)
    return num_kv_heads % n == 0 and num_kv_heads >= n


# The COMPLETE cross-shard communication contract of the serving engine,
# co-located with the sharding scheme it belongs to.  Head-sharded pool
# planes stay bit-identical to a 1-device run because the only staged
# collectives are (a) the tiled attention-head ``all_gather`` — pure data
# movement, exact at any dtype — and (b) the integer ``psum`` that ORs
# per-shard COW dirty masks.  NO float reduction may cross shards: float
# summation is reduction-order-dependent, which would break the trace
# suite's mesh-parity gate.  ``repro.analysis.contracts`` turns this into
# the CollectiveRule every engine entry point is audited against.
SERVE_MOVEMENT_COLLECTIVES = ("all_gather",)
SERVE_INTEGER_REDUCTIONS = ("psum",)
SERVE_FLOAT_REDUCTIONS: tuple = ()


def serve_collective_whitelist() -> dict:
    """{"movement", "integer_reductions", "float_reductions"} — the
    collectives the serving engine's compiled paths may stage."""
    return {"movement": SERVE_MOVEMENT_COLLECTIVES,
            "integer_reductions": SERVE_INTEGER_REDUCTIONS,
            "float_reductions": SERVE_FLOAT_REDUCTIONS}


# ---------------------------------------------------------------------------
# in-graph sharding constraints (GSPMD guidance)
# ---------------------------------------------------------------------------
# GSPMD occasionally replicates large activations rather than keep the batch
# sharded through a scan, and routes MoE dispatch through all-reduces instead
# of all-to-alls (measured in EXPERIMENTS.md §Perf iteration on llama4).
# Layers call ``constrain(x, "dp", None, "model")`` with symbolic axes; the
# launcher installs the concrete mesh.  Without an installed mesh (CPU unit
# tests) this is a no-op.

_CONSTRAINT_MESH: list = [None]


def set_constraint_mesh(mesh) -> None:
    _CONSTRAINT_MESH[0] = mesh


def constrain(x, *axes):
    import os
    mesh = _CONSTRAINT_MESH[0]
    if mesh is None or os.environ.get("REPRO_NO_CONSTRAIN"):
        return x
    resolved = []
    for dim, ax in zip(x.shape, axes):
        if ax == "dp":
            ax = dp_axes(mesh)
        elif ax == "fsdp":
            ax = fsdp_axes(mesh)
        if ax is not None and not _fits(dim, mesh, ax):
            ax = None
        resolved.append(ax)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*resolved)))
