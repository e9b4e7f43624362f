"""Continuous Thinking (CT) paged KV cache (paper Sec. 5).

A PagedAttention-style pool extended with ThinKV's block-table fields:
thought type, segment identity, and an eviction state that lets evicted
slots be *reused in place* by later tokens — never gather-compacted.

TPU adaptations (DESIGN.md Sec. 3):
* block size 16 == quantization group g; the planes keep the head axis
  AHEAD of the block's token axis, so one block of one head is a
  contiguous (BS, D) page — the tile the paged kernels stream;
* "start indices + segment mask" are fused into a per-slot ``slot_seg``
  plane; the eviction mask is the per-slot ``slot_state`` plane
  (0=free, 1=valid, 2=soft-evicted/reusable);
* per-slot ``slot_bits`` makes decode correctness independent of block
  type-homogeneity (homogeneity remains the allocation *policy*, as in the
  paper, but a pathological allocation can fall back to cross-type reuse
  without corrupting decodes);
* scales are E4M3-rounded values stored in bf16 planes (bit-exact e4m3
  numerics; accounted as 1 byte in the memory model — see DESIGN.md Sec. 7).

Data model (this PR's paged refactor):

* :class:`PoolView` holds the HEAVY planes (nibble codes + group scales) in
  **paged layout** ``[L, num_blocks, H, block_size, ...]`` — the exact
  layout the ``ct_paged_attention`` kernels stream from HBM.  Logical slot
  ``s`` of a layer lives at ``[s // BS, :, s % BS]`` (:func:`slots_take`
  / :func:`slots_put`).
* :class:`CTCache` holds only per-request METADATA (slot/segment state,
  thought bookkeeping) and the full-precision TBQ buffer.  Metadata planes
  stay flat ``[L, NS]`` (NS = num_blocks * block_size) because the
  allocation/annealing logic addresses logical slots linearly.
* :class:`GlobalPool` is the serving engine's SHARED physical pool: one
  PoolView of ``NP`` physical blocks plus a per-layer block REFCOUNT
  (free ⇔ refcount 0), with per-request per-layer block tables (``-1`` =
  unmapped) translating logical blocks to physical blocks.  Requests
  claim physical blocks at group commits and decref them when TBE frees
  a block (or the request retires), so slots freed by one request are
  reused by others — vLLM-style paging on top of CT's in-place slot
  reuse.  A block mapped by MORE than one holder (prefix-cache sharing)
  has refcount > 1 and is copy-on-write: any content mutation claims a
  fresh block, copies the planes, and decrefs the shared source
  (:func:`sync_block_tables` with a dirty mask / :func:`cow_blocks`).

All state is fixed-shape and jit/vmap friendly.  Functions here operate on a
SINGLE request with all attention layers stacked on the leading axis; the
serving engine vmaps/scans over request slots.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.config import ThinKVConfig, ThoughtType
from repro.core import quantization as Q
from repro.core.policy import get_policy
from repro.core.thoughts import classify
from repro.serving import tracing as TR

SCALE_DTYPE = jnp.bfloat16      # e4m3-rounded values (see module docstring)

FREE, VALID, EVICTED = jnp.uint8(0), jnp.uint8(1), jnp.uint8(2)

UNMAPPED = jnp.int32(-1)        # block-table entry with no physical block


class CacheDims(NamedTuple):
    """Static geometry of a CT cache."""

    L: int          # attention layers
    NB: int         # logical blocks per layer per request
    BS: int         # block size (tokens)
    H: int          # kv heads
    D: int          # head dim
    G: int          # quantization group size (== tokens per commit)
    S: int          # max segments
    nibble: bool    # True: 4-bit plane (2 codes/byte would be packed on HBM;
                    # we keep one code per uint8 lane and account 4 bits)

    @property
    def NS(self) -> int:
        return self.NB * self.BS

    @property
    def scale_groups(self) -> int:
        return self.D // Q.GROUP


def make_dims(cfg: ThinKVConfig, num_layers: int, kv_heads: int,
              head_dim: int, slack: float = 2.0) -> CacheDims:
    nb = max(int(cfg.token_budget * slack) // cfg.block_size, 4)
    nibble = max(cfg.precision) <= 4
    return CacheDims(L=num_layers, NB=nb, BS=cfg.block_size, H=kv_heads,
                     D=head_dim, G=cfg.group_size, S=cfg.max_segments,
                     nibble=nibble)


# ---------------------------------------------------------------------------
# Pool planes (paged layout) and per-request metadata
# ---------------------------------------------------------------------------

class PoolView(NamedTuple):
    """Quantized KV planes in paged layout.

    Per-request views have ``num_blocks == dims.NB``; the engine's shared
    :class:`GlobalPool` holds the same planes with ``NP`` physical blocks.
    """

    k_codes: jax.Array      # [L, nb, H, BS, D] uint8
    v_codes: jax.Array      # [L, nb, H, BS, D] uint8
    k_scales: jax.Array     # [L, nb, H, BS, D//GROUP] bf16 (e4m3-valued)
    v_scales: jax.Array     # [L, nb, H, BS, D//GROUP] bf16


def init_pool_view(dims: CacheDims, num_blocks: int | None = None
                   ) -> PoolView:
    nb = dims.NB if num_blocks is None else num_blocks
    L, BS, H, D = dims.L, dims.BS, dims.H, dims.D
    sg = dims.scale_groups
    return PoolView(
        k_codes=jnp.zeros((L, nb, H, BS, D), jnp.uint8),
        v_codes=jnp.zeros((L, nb, H, BS, D), jnp.uint8),
        k_scales=jnp.zeros((L, nb, H, BS, sg), SCALE_DTYPE),
        v_scales=jnp.zeros((L, nb, H, BS, sg), SCALE_DTYPE),
    )


def slots_take(plane: jax.Array, idx: jax.Array) -> jax.Array:
    """One layer's paged plane ``[nb, H, BS, X]`` at logical slots ``idx``
    ``[n]`` -> ``[n, H, X]``."""
    bs = plane.shape[2]
    return plane[idx // bs, :, idx % bs]


def slots_put(plane: jax.Array, idx: jax.Array, val: jax.Array
              ) -> jax.Array:
    """Write ``val`` ``[n, H, X]`` into logical slots ``idx`` of one
    layer's paged plane ``[nb, H, BS, X]``."""
    bs = plane.shape[2]
    return plane.at[idx // bs, :, idx % bs].set(val)


def page_tokens(pages: jax.Array) -> jax.Array:
    """Paged planes ``[..., nb, H, BS, X]`` -> token-major
    ``[..., nb * BS, H, X]`` (the dense readers' layout)."""
    x = jnp.swapaxes(pages, -3, -2)
    return x.reshape(*x.shape[:-4], x.shape[-4] * x.shape[-3],
                     *x.shape[-2:])


@jax.tree_util.register_pytree_node_class
class CTCache:
    """Pytree of per-request cache metadata + TBQ buffer for one request."""

    FIELDS = ("slot_state", "slot_seg", "slot_pos", "slot_bits",
              "block_type", "seg_type", "seg_level", "buf_k", "buf_v",
              "buf_len", "cur_seg", "cur_thought", "prev_thought",
              "num_tokens")

    def __init__(self, **kw):
        for f in self.FIELDS:
            setattr(self, f, kw[f])

    def tree_flatten(self):
        return tuple(getattr(self, f) for f in self.FIELDS), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(**dict(zip(cls.FIELDS, children)))

    def replace(self, **kw) -> "CTCache":
        d = {f: getattr(self, f) for f in self.FIELDS}
        d.update(kw)
        return CTCache(**d)


def init_cache(dims: CacheDims) -> CTCache:
    """Empty cache metadata; segment 0 opens as REASONING (prefill tokens
    are treated as R-type, paper Sec. 6.1)."""
    L, NS, H, D, G, S = dims.L, dims.NS, dims.H, dims.D, dims.G, dims.S
    seg_type = jnp.full((S,), -1, jnp.int32).at[0].set(
        jnp.int32(ThoughtType.REASONING))
    return CTCache(
        slot_state=jnp.zeros((L, NS), jnp.uint8),
        slot_seg=jnp.full((L, NS), -1, jnp.int32),
        slot_pos=jnp.full((L, NS), -1, jnp.int32),
        slot_bits=jnp.full((L, NS), 4, jnp.uint8),
        block_type=jnp.full((L, dims.NB), -1, jnp.int8),
        seg_type=seg_type,
        seg_level=jnp.zeros((L, S), jnp.int32),
        buf_k=jnp.zeros((L, G, H, D), jnp.bfloat16),
        buf_v=jnp.zeros((L, G, H, D), jnp.bfloat16),
        buf_len=jnp.int32(0),
        cur_seg=jnp.int32(0),
        cur_thought=jnp.int32(ThoughtType.REASONING),
        prev_thought=jnp.int32(ThoughtType.REASONING),
        num_tokens=jnp.int32(0),
    )


# ---------------------------------------------------------------------------
# Commit: quantize a full buffer group and place it (TBQ + CT step a/b/d)
# ---------------------------------------------------------------------------

def _quantize_group_by_thought(cfg: ThinKVConfig, k: jax.Array, v: jax.Array,
                               thought: jax.Array, policy=None):
    """Quantize [G,H,D] K/V at psi(thought) bits.  bits is traced, so all
    of the policy's precision levels are computed (G=16 tokens —
    negligible) and selected."""
    policy = get_policy(policy)
    bits = policy.psi_bits(thought, cfg)
    uniq = policy.precision_levels(cfg)
    outs = [(b, Q.quantize_group(k, b), Q.quantize_group(v, b)) for b in uniq]
    kc, ks = outs[0][1]
    vc, vs = outs[0][2]
    for b, (kc2, ks2), (vc2, vs2) in outs[1:]:
        sel = bits == b
        kc = jnp.where(sel, kc2, kc)
        ks = jnp.where(sel, ks2, ks)
        vc = jnp.where(sel, vc2, vc)
        vs = jnp.where(sel, vs2, vs)
    return kc, ks.astype(SCALE_DTYPE), vc, vs.astype(SCALE_DTYPE), bits


def _alloc_slots_one_layer(dims: CacheDims, slot_state, block_type, thought):
    """Pick G logical slot addresses for a group of thought type t.

    Priority (paper Sec. 5.2 walkthrough):
      4 — evicted slot in a same-type block (in-place reuse)
      3 — free slot in a same-type, partially-filled block
      2 — slot in a fully-free block (claim new block)
      1 — evicted slot in an other-type block (emergency fallback; decode
          stays correct thanks to per-slot bits)
    Ties broken by ascending linear address so claimed fresh blocks fill
    contiguously.
    """
    NS, BS = dims.NS, dims.BS
    btype = jnp.repeat(block_type, BS)                         # [NS]
    same = btype == thought.astype(block_type.dtype)
    block_free = jnp.repeat(
        jnp.all((slot_state.reshape(dims.NB, BS) == FREE), axis=1), BS)
    score = jnp.zeros((NS,), jnp.int32)
    score = jnp.where(block_free, 2, score)
    score = jnp.where((slot_state == FREE) & same & ~block_free, 3, score)
    score = jnp.where((slot_state == EVICTED) & same, 4, score)
    score = jnp.where((slot_state == EVICTED) & ~same, 1, score)
    lin = jnp.arange(NS, dtype=jnp.int32)
    key = score * NS - lin                                     # max = best
    _, idx = jax.lax.top_k(key, dims.G)
    ok = score[idx] > 0                                        # per-slot valid
    return idx, ok


def commit_group(cfg: ThinKVConfig, dims: CacheDims, cache: CTCache,
                 view: PoolView, policy=None) -> Tuple[CTCache, PoolView]:
    """Quantize the (full) buffer and write it into the pool, reusing evicted
    slots in place.  vmapped over layers."""
    policy = get_policy(policy)
    t = cache.cur_thought
    positions = cache.num_tokens - dims.G + jnp.arange(dims.G, dtype=jnp.int32)

    def one_layer(buf_k, buf_v, k_codes, v_codes, k_scales, v_scales,
                  slot_state, slot_seg, slot_pos, slot_bits, block_type):
        kc, ks, vc, vs, bits = _quantize_group_by_thought(cfg, buf_k, buf_v, t,
                                                          policy)
        idx, ok = _alloc_slots_one_layer(dims, slot_state, block_type, t)
        # guard: never write through invalid addresses (ok False is a
        # capacity bug surfaced via cache_pressure metrics, not corruption)
        safe = jnp.where(ok, idx, 0)
        upd = lambda plane, val: slots_put(
            plane, safe, jnp.where(ok[:, None, None], val,
                                   slots_take(plane, safe)))
        k_codes = upd(k_codes, kc)
        v_codes = upd(v_codes, vc)
        k_scales = upd(k_scales, ks)
        v_scales = upd(v_scales, vs)
        slot_state = slot_state.at[safe].set(
            jnp.where(ok, VALID, slot_state[safe]))
        slot_seg = slot_seg.at[safe].set(
            jnp.where(ok, cache.cur_seg, slot_seg[safe]))
        slot_pos = slot_pos.at[safe].set(jnp.where(ok, positions,
                                                   slot_pos[safe]))
        slot_bits = slot_bits.at[safe].set(
            jnp.where(ok, bits.astype(jnp.uint8), slot_bits[safe]))
        # claim fresh blocks for the thought type
        bidx = safe // dims.BS
        claim = ok & (block_type[bidx] == -1)
        block_type = block_type.at[bidx].set(
            jnp.where(claim, t.astype(block_type.dtype), block_type[bidx]))
        return (k_codes, v_codes, k_scales, v_scales, slot_state, slot_seg,
                slot_pos, slot_bits, block_type)

    outs = jax.vmap(one_layer)(
        cache.buf_k.astype(jnp.float32), cache.buf_v.astype(jnp.float32),
        *view, cache.slot_state, cache.slot_seg, cache.slot_pos,
        cache.slot_bits, cache.block_type)
    (k_codes, v_codes, k_scales, v_scales, slot_state, slot_seg, slot_pos,
     slot_bits, block_type) = outs
    cache = cache.replace(
        slot_state=slot_state, slot_seg=slot_seg, slot_pos=slot_pos,
        slot_bits=slot_bits, block_type=block_type, buf_len=jnp.int32(0))
    return cache, PoolView(k_codes, v_codes, k_scales, v_scales)


def commit_and_evict_if_full(cfg: ThinKVConfig, dims: CacheDims,
                             cache: CTCache, view: PoolView,
                             axis_name: str | None = None,
                             policy=None) -> Tuple[CTCache, PoolView]:
    """Commit the buffer as a group and enforce the per-layer budget when
    the buffer is full (paper Listing 1 checks `kv_size(l) > budget` in the
    step loop; the cache only grows at commits, so commit time is the
    faithful check point)."""
    policy = get_policy(policy)

    def do_commit(args):
        c, v = args
        c, v = commit_group(cfg, dims, c, v, policy)
        return budget_evict(cfg, dims, c, v, axis_name=axis_name,
                            policy=policy), v

    return jax.lax.cond(cache.buf_len >= dims.G, do_commit, lambda a: a,
                        (cache, view))


def append_token(cfg: ThinKVConfig, dims: CacheDims, cache: CTCache,
                 view: PoolView, k_t: jax.Array, v_t: jax.Array,
                 policy=None) -> Tuple[CTCache, PoolView]:
    """Append one token's [L,H,D] KV to the fp buffer; commit when full."""
    i = cache.buf_len
    cache = cache.replace(
        buf_k=jax.lax.dynamic_update_index_in_dim(
            cache.buf_k, k_t.astype(jnp.bfloat16)[:, None], i, axis=1),
        buf_v=jax.lax.dynamic_update_index_in_dim(
            cache.buf_v, v_t.astype(jnp.bfloat16)[:, None], i, axis=1),
        buf_len=i + 1,
        num_tokens=cache.num_tokens + 1,
    )
    return commit_and_evict_if_full(cfg, dims, cache, view, policy=policy)


# ---------------------------------------------------------------------------
# head-axis sharding hooks (serving engine's shard_map tensor parallelism)
# ---------------------------------------------------------------------------
# Inside the engine's shard_map, every plane carries only this shard's KV
# heads while all metadata is replicated.  Almost every CT op is head-local
# (quantization groups run along head_dim inside one head; slot allocation
# reads metadata only), so per-shard execution reproduces the single-device
# metadata decisions exactly.  The TWO cross-head computations gather
# explicitly — all_gather is pure data movement and integer psum is
# order-free, so the sharded run stays BIT-IDENTICAL to 1-device:
#   * TBE annealing clusters keys FLATTENED OVER HEADS (kmeans over
#     [cap, H*D]) — the segment's local keys are gathered to full H first;
#   * the COW dirty detector compares plane content — a slot dirty in any
#     shard's heads must fault on every shard (mask OR-reduced by psum).


def gather_heads(x: jax.Array, axis_name: str | None, axis: int
                 ) -> jax.Array:
    """All-gather the sharded head axis (no-op when ``axis_name`` is None —
    the single-device path compiles collective-free)."""
    if axis_name is None:
        return x
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=True)


def _any_shard(mask: jax.Array, axis_name: str | None) -> jax.Array:
    """Cross-shard OR of a boolean mask (deterministic: integer psum)."""
    if axis_name is None:
        return mask
    return jax.lax.psum(mask.astype(jnp.int32), axis_name) > 0


# ---------------------------------------------------------------------------
# TBE: segment annealing + budget eviction (paper Sec. 4.3)
# ---------------------------------------------------------------------------

def _segment_tokens(dims: CacheDims, slot_seg, slot_state, seg: jax.Array):
    """Addresses of the valid tokens of segment ``seg`` (fixed cap =
    refresh_interval... bounded by G*ceil(tau/G); we use cap=128)."""
    cap = 128
    match = (slot_seg == seg) & (slot_state == VALID)
    order = jnp.where(match, jnp.arange(dims.NS), dims.NS + 1)
    idx = jnp.argsort(order)[:cap]
    valid = jnp.take(match, idx)
    return idx, valid


def _anneal_one_segment(cfg: ThinKVConfig, dims: CacheDims, seg: jax.Array,
                        enable: jax.Array, k_codes, k_scales, slot_state,
                        slot_seg, slot_bits, seg_level_row,
                        axis_name: str | None = None, policy=None):
    """Anneal segment ``seg`` one retention level in ONE layer.  Returns
    updated (slot_state, seg_level_row).  ``k_codes``/``k_scales`` are the
    layer's paged [nb, H, BS, ...] planes (this shard's heads when
    ``axis_name`` is set — the selection keys are gathered to the FULL
    head set so every shard makes the same eviction decision as a single
    device would)."""
    policy = get_policy(policy)
    idx, valid = _segment_tokens(dims, slot_seg, slot_state, seg)
    level = seg_level_row[seg]
    target = policy.retention_at(level, cfg)
    count = jnp.sum(valid.astype(jnp.int32))
    do = enable & (count > 0)

    # dequantized post-RoPE keys of the segment, flattened over heads
    kc = slots_take(k_codes, idx)                         # [cap,H,D]
    ks = slots_take(k_scales, idx)
    bits = jnp.take(slot_bits, idx, axis=0)               # [cap]
    keys = Q.dequantize_by_bitcode(
        kc, ks.astype(jnp.float32),
        bits[:, None, None].astype(jnp.int32))            # [cap,H,D]
    keys = gather_heads(keys, axis_name, axis=1)          # shard -> full H
    keys = keys.reshape(keys.shape[0], -1)

    keep_mask = policy.select_tokens(keys, valid, target, cfg)
    evict = valid & ~keep_mask & do & (count > target)
    # when count <= target nothing is evicted but the level still advances
    new_state = slot_state.at[idx].set(
        jnp.where(evict, EVICTED, slot_state[idx]))
    new_level = seg_level_row.at[seg].set(
        jnp.where(do, jnp.minimum(level + 1,
                                  len(cfg.retention_schedule) - 1 + 1),
                  level))
    return new_state, new_level


def _free_empty_blocks(dims: CacheDims, slot_state, block_type):
    """Blocks with no VALID slot return to the free pool (their EVICTED slots
    become FREE) — bounds fragmentation without any data movement."""
    by_block = slot_state.reshape(dims.NB, dims.BS)
    empty = ~jnp.any(by_block == VALID, axis=1)
    by_block = jnp.where(empty[:, None], FREE, by_block)
    block_type = jnp.where(empty, jnp.int8(-1), block_type)
    return by_block.reshape(dims.NS), block_type


def tbe_anneal_all(cfg: ThinKVConfig, dims: CacheDims, cache: CTCache,
                   view: PoolView, before_seg: jax.Array,
                   axis_name: str | None = None, policy=None) -> CTCache:
    """Case 1: a transition segment ended — anneal every preceding segment
    (including previous transitions) one retention level, in every layer."""
    policy = get_policy(policy)

    def one_layer(k_codes, k_scales, slot_state, slot_seg, slot_bits,
                  seg_level_row):
        def body(carry, seg):
            slot_state, seg_level_row = carry
            enable = (seg < before_seg) & (cache.seg_type[seg] >= 0)
            slot_state, seg_level_row = _anneal_one_segment(
                cfg, dims, seg, enable, k_codes, k_scales, slot_state,
                slot_seg, slot_bits, seg_level_row, axis_name, policy)
            return (slot_state, seg_level_row), None

        (slot_state, seg_level_row), _ = jax.lax.scan(
            body, (slot_state, seg_level_row),
            jnp.arange(dims.S, dtype=jnp.int32))
        return slot_state, seg_level_row

    slot_state, seg_level = jax.vmap(one_layer)(
        view.k_codes, view.k_scales, cache.slot_state, cache.slot_seg,
        cache.slot_bits, cache.seg_level)
    slot_state, block_type = jax.vmap(
        lambda s, b: _free_empty_blocks(dims, s, b))(slot_state,
                                                     cache.block_type)
    return cache.replace(slot_state=slot_state, seg_level=seg_level,
                         block_type=block_type)


def budget_evict(cfg: ThinKVConfig, dims: CacheDims, cache: CTCache,
                 view: PoolView, max_rounds: int = 4,
                 axis_name: str | None = None, policy=None) -> CTCache:
    """Case 2: cache above budget with no transition — anneal the oldest,
    least-important segment one level per round until within budget."""
    policy = get_policy(policy)

    def one_layer(k_codes, k_scales, slot_state, slot_seg, slot_bits,
                  seg_level_row):
        def round_body(_, carry):
            slot_state, seg_level_row = carry
            n_valid = jnp.sum((slot_state == VALID).astype(jnp.int32))
            over = n_valid > cfg.token_budget

            def do(carry):
                slot_state, seg_level_row = carry
                # per-segment current counts (only paid when over budget)
                seg_ids = jnp.arange(dims.S, dtype=jnp.int32)
                seg_of_slot = jnp.where(slot_state == VALID, slot_seg, -1)
                counts = jnp.zeros((dims.S,), jnp.int32).at[seg_of_slot].add(
                    1, mode="drop")
                shrinkable = (counts > cfg.min_retention) & \
                    (cache.seg_type >= 0) & (seg_ids < cache.cur_seg)
                # least important first (policy rho), then oldest; the
                # default rho IS the seg_type value (T=0 < E=1 < R=2)
                key = policy.rho(cache.seg_type) * dims.S + seg_ids
                key = jnp.where(shrinkable, key, jnp.int32(2 ** 30))
                seg = jnp.argmin(key)
                enable = jnp.any(shrinkable)
                return _anneal_one_segment(
                    cfg, dims, seg, enable, k_codes, k_scales, slot_state,
                    slot_seg, slot_bits, seg_level_row, axis_name, policy)

            return jax.lax.cond(over, do, lambda c: c,
                                (slot_state, seg_level_row))

        slot_state, seg_level_row = jax.lax.fori_loop(
            0, max_rounds, round_body, (slot_state, seg_level_row))
        return slot_state, seg_level_row

    slot_state, seg_level = jax.vmap(one_layer)(
        view.k_codes, view.k_scales, cache.slot_state, cache.slot_seg,
        cache.slot_bits, cache.seg_level)
    slot_state, block_type = jax.vmap(
        lambda s, b: _free_empty_blocks(dims, s, b))(slot_state,
                                                     cache.block_type)
    return cache.replace(slot_state=slot_state, seg_level=seg_level,
                         block_type=block_type)


# ---------------------------------------------------------------------------
# Refresh (thought classification + segment roll, paper Sec. 4.1/Listing 1)
# ---------------------------------------------------------------------------

def refresh(cfg: ThinKVConfig, dims: CacheDims, cache: CTCache,
            view: PoolView, sparsity: jax.Array,
            axis_name: str | None = None, policy=None) -> CTCache:
    """Every tau steps: classify the sparsity into a thought type, close the
    current segment, trigger TBE if the closing segment was a transition,
    then enforce the budget.  Thought classification is policy-independent
    (it measures the MODEL); what a policy changes is how each thought is
    quantized, selected, and evicted."""
    policy = get_policy(policy)
    new_thought = classify(sparsity, cfg.sparsity_thresholds)
    ended_seg = cache.cur_seg
    ended_type = cache.seg_type[ended_seg]

    cache = jax.lax.cond(
        ended_type == jnp.int32(ThoughtType.TRANSITION),
        lambda c: tbe_anneal_all(cfg, dims, c, view, before_seg=ended_seg,
                                 axis_name=axis_name, policy=policy),
        lambda c: c, cache)

    nxt = jnp.minimum(ended_seg + 1, dims.S - 1)
    cache = cache.replace(
        cur_seg=nxt,
        seg_type=cache.seg_type.at[nxt].set(new_thought),
        prev_thought=cache.cur_thought,
        cur_thought=new_thought,
    )
    return budget_evict(cfg, dims, cache, view, axis_name=axis_name,
                        policy=policy)


# ---------------------------------------------------------------------------
# Shared global block pool (engine-level paging across request slots)
# ---------------------------------------------------------------------------

class GlobalPool(NamedTuple):
    """Physical block pool shared by every request slot.

    ``view`` planes are ``[L, NP, H, BS, ...]``; ``refcount`` is a per-layer
    per-physical-block REFERENCE COUNT (free ⇔ refcount 0).  Per-request
    per-layer block tables (``[L, NB]`` int32, UNMAPPED = -1) live with
    the engine; each mapped table entry holds one reference, and the
    engine's prefix cache holds one reference per registered entry that
    maps the block.  A block with refcount > 1 is SHARED: its planes are
    immutable, and any writer must copy-on-write first (claim a fresh
    block, copy the planes, swap its table entry, decref the source —
    see :func:`sync_block_tables` / :func:`cow_blocks`).
    """

    view: PoolView
    refcount: jax.Array     # [L, NP] int32; 0 == free

    @property
    def free(self) -> jax.Array:
        """Per-layer free bitmap [L, NP] (derived: refcount == 0)."""
        return self.refcount == 0


def init_global_pool(dims: CacheDims, num_blocks: int) -> GlobalPool:
    return GlobalPool(
        view=init_pool_view(dims, num_blocks),
        refcount=jnp.zeros((dims.L, num_blocks), jnp.int32),
    )


def init_block_table(dims: CacheDims) -> jax.Array:
    return jnp.full((dims.L, dims.NB), UNMAPPED, jnp.int32)


def stacked_slot_plane(dims: CacheDims, plane: jax.Array) -> jax.Array:
    """Engine metadata [R, L, NS] -> the fused kernel's [L, R, NB, BS]."""
    r = plane.shape[0]
    return jnp.swapaxes(plane, 0, 1).reshape(dims.L, r, dims.NB, dims.BS)


def stacked_buffers(buf: jax.Array) -> jax.Array:
    """Engine TBQ buffers [R, L, G, H, D] -> the fused kernel's
    [L, R, H, G, D] (one head's G tokens form one (G, D) tile)."""
    return jnp.transpose(buf, (1, 0, 3, 2, 4))


def gather_view(pool_view: PoolView, table: jax.Array) -> PoolView:
    """Per-request paged view through a [L, NB] block table.

    Unmapped entries gather block 0 — their contents are irrelevant because
    every slot of an unmapped logical block is FREE in the metadata.
    """
    safe = jnp.maximum(table, 0)

    def g(plane):
        return jax.vmap(lambda p, t: p[t])(plane, safe)
    return PoolView(*(g(p) for p in pool_view))


def scatter_view(pool_view: PoolView, table: jax.Array, view: PoolView
                 ) -> PoolView:
    """Write a per-request view back through its table (unmapped dropped)."""
    np_blocks = pool_view.k_codes.shape[1]
    idx = jnp.where(table >= 0, table, np_blocks)       # OOB -> dropped

    def s(plane, vplane):
        return jax.vmap(
            lambda p, t, v: p.at[t].set(v, mode="drop"))(plane, idx, vplane)
    return PoolView(*(s(p, v) for p, v in zip(pool_view, view)))


def changed_slots(view_old: PoolView, view_new: PoolView) -> jax.Array:
    """Per-slot content-change mask ``[L, NS]`` between two per-request
    views (the COW dirty detector: a slot is dirty iff ANY of its four
    planes differ — content-based, so a write of identical bytes is not a
    mutation and needs no copy)."""
    def per(a, b):
        L, nb, _, bs = a.shape[:4]
        return jnp.any(a != b, axis=(2, 4)).reshape(L, nb * bs)
    out = per(view_old[0], view_new[0])
    for a, b in zip(view_old[1:], view_new[1:]):
        out = out | per(a, b)
    return out


def _rank_alloc(np_blocks: int, rc_row: jax.Array, need: jax.Array):
    """Allocate free physical ids (refcount 0, ascending) to the True
    entries of ``need``; returns (cand, got) — rank i of ``need`` gets the
    i-th free id, ``got`` marks satisfied entries."""
    free_row = rc_row == 0
    order = jnp.where(free_row, jnp.arange(np_blocks, dtype=jnp.int32),
                      jnp.int32(np_blocks + 1))
    free_sorted = jnp.argsort(order).astype(jnp.int32)
    n_free = jnp.sum(free_row.astype(jnp.int32))
    rank = jnp.cumsum(need.astype(jnp.int32)) - 1
    cand = free_sorted[jnp.clip(rank, 0, np_blocks - 1)]
    got = need & (rank < n_free)
    return cand, got


def sync_block_tables(dims: CacheDims, pool: GlobalPool, table: jax.Array,
                      cache: CTCache, view: PoolView,
                      dirty_slots: jax.Array | None = None):
    """Reconcile a request's logical blocks with the physical pool after a
    CT update: decref released blocks (free at refcount 0), COW-fault any
    SHARED block whose content this update changed, map newly claimed
    logical blocks to free physical ids (lowest first), scatter the view
    back, and revert any logical claims the pool could not back
    (allocation failure under oversubscription — surfaced as still-FREE
    slots, never corruption).

    ``dirty_slots`` is the ``[L, NS]`` content-change mask from
    :func:`changed_slots` (None: no writes happened, COW cannot trigger).
    A dirty block whose physical refcount is > 1 is COW-faulted: the
    shared source is decref'd, a fresh block claimed, and the scatter
    writes the request's full (old + newly written) block content into
    the copy — the shared source's planes are NEVER written.  If the COW
    claim cannot be backed, the old mapping is re-attached (incref), the
    scatter masked for that block, and the dirty slots reverted to FREE:
    the shared content stays pristine even on failure.

    Returns ``(pool, table, cache, alloc_failed, cow)``; ``alloc_failed``
    and ``cow`` are ``[L, NB]`` masks.  The serving engine guarantees
    ``alloc_failed`` stays all-False by preempting requests BEFORE a
    commit that the free list cannot back (see
    ``ThinKVEngine._ensure_decode_headroom``, whose demand bound counts a
    committing slot's shared blocks as potential COW claims); it is
    surfaced so the engine can assert the guarantee rather than silently
    dropping data.
    """
    np_blocks = pool.refcount.shape[1]
    new_bt = cache.block_type
    if dirty_slots is None:
        dirty_blocks = jnp.zeros(table.shape, bool)
        dirty_slots = jnp.zeros((table.shape[0], dims.NS), bool)
    else:
        dirty_blocks = jnp.any(
            dirty_slots.reshape(table.shape[0], dims.NB, dims.BS), axis=-1)

    def one_layer(rc_row, table_row, new_row, dirty_row):
        # 1) logical frees (TBE emptied the block / request released it):
        #    decref — the block returns to the free list only at zero
        freed = (new_row == -1) & (table_row >= 0)
        rc_row = rc_row.at[jnp.where(freed, table_row, np_blocks)].add(
            -1, mode="drop")
        table_row = jnp.where(freed, UNMAPPED, table_row)

        # 2) COW faults: mapped + content changed + shared (refcount > 1)
        phys = jnp.where(table_row >= 0, table_row, 0)
        cow = (table_row >= 0) & dirty_row & (rc_row[phys] > 1)
        old_phys = jnp.where(cow, table_row, UNMAPPED)
        rc_row = rc_row.at[jnp.where(cow, table_row, np_blocks)].add(
            -1, mode="drop")
        table_row = jnp.where(cow, UNMAPPED, table_row)

        # 3) claim free physical ids for fresh logical claims + COW copies
        need = (new_row >= 0) & (table_row < 0)
        cand, got = _rank_alloc(np_blocks, rc_row, need)
        table_row = jnp.where(got, cand, table_row)
        rc_row = rc_row.at[jnp.where(got, cand, np_blocks)].add(
            1, mode="drop")

        # 4) a COW claim that failed re-attaches the (still-live) source
        failed_cow = cow & ~got
        table_row = jnp.where(failed_cow, old_phys, table_row)
        rc_row = rc_row.at[jnp.where(failed_cow, old_phys, np_blocks)].add(
            1, mode="drop")
        alloc_failed = need & ~got
        return rc_row, table_row, alloc_failed, failed_cow, cow & got

    refcount, table, alloc_failed, failed_cow, cow = jax.vmap(one_layer)(
        pool.refcount, table, new_bt, dirty_blocks)

    # revert claims that could not be backed.  A failed FRESH claim holds
    # only this update's writes — every slot of the block reverts to FREE
    # and the logical block is un-claimed.  A failed COW keeps the shared
    # mapping and its pre-existing valid slots; only the DIRTY slots (the
    # writes that never landed) revert.
    fresh_failed = alloc_failed & ~failed_cow
    failed_slots = jnp.repeat(fresh_failed, dims.BS, axis=1) | \
        (jnp.repeat(failed_cow, dims.BS, axis=1) & dirty_slots)   # [L, NS]
    cache = cache.replace(
        slot_state=jnp.where(failed_slots, FREE, cache.slot_state),
        block_type=jnp.where(fresh_failed, jnp.int8(-1), cache.block_type))

    # scatter through the post-COW table; a failed COW's block is masked
    # so the shared source's planes are never written with changed content
    scatter_table = jnp.where(failed_cow, UNMAPPED, table)
    pool_view = scatter_view(pool.view, scatter_table, view)
    return (GlobalPool(view=pool_view, refcount=refcount), table, cache,
            alloc_failed, cow)


def release_blocks(dims: CacheDims, pool: GlobalPool, table: jax.Array
                   ) -> GlobalPool:
    """Drop one reference on every mapped block of ``table`` (a retiring
    or spilling request, or a prefix-cache entry being evicted); a block
    returns to the free list when its refcount reaches zero."""
    np_blocks = pool.refcount.shape[1]
    idx = jnp.where(table >= 0, table, np_blocks)
    refcount = jax.vmap(lambda r, t: r.at[t].add(-1, mode="drop"))(
        pool.refcount, idx)
    return GlobalPool(view=pool.view, refcount=refcount)


def incref_blocks(dims: CacheDims, pool: GlobalPool, table: jax.Array
                  ) -> GlobalPool:
    """Add one reference to every mapped block of ``table`` — a new holder
    (a prefix-cache hit mapping shared blocks into its block table, or a
    prefix-cache registration) pins the blocks' content: any later writer
    must COW-fault instead of mutating them in place."""
    np_blocks = pool.refcount.shape[1]
    idx = jnp.where(table >= 0, table, np_blocks)
    refcount = jax.vmap(lambda r, t: r.at[t].add(1, mode="drop"))(
        pool.refcount, idx)
    return GlobalPool(view=pool.view, refcount=refcount)


def cow_blocks(dims: CacheDims, pool: GlobalPool, table: jax.Array,
               mask: jax.Array) -> Tuple[GlobalPool, jax.Array, jax.Array]:
    """Explicit copy-on-write fault for the masked mapped SHARED logical
    blocks: claim a fresh physical block each, copy the planes, swap the
    table entries, decref the shared sources.  Masked blocks this table
    owns exclusively (refcount 1) are skipped — the sole owner may write
    in place, and COWing them would put the just-decref'd source on the
    free list where another masked block's copy could claim it within
    this very call (aliasing two logical blocks onto one physical id if
    the original's own claim then failed).  The refcount > 1 guard makes
    a selected source's post-decref count >= 1, so sources can never be
    reallocated mid-call.  Returns ``(pool, table, ok)`` — on a failed
    claim the old mapping is re-attached (the source stays live and
    unwritten) and ``ok`` is False."""
    np_blocks = pool.refcount.shape[1]
    view = gather_view(pool.view, table)

    def one_layer(rc_row, table_row, m_row):
        phys = jnp.where(table_row >= 0, table_row, 0)
        sel = m_row & (table_row >= 0) & (rc_row[phys] > 1)
        old_phys = jnp.where(sel, table_row, UNMAPPED)
        rc_row = rc_row.at[jnp.where(sel, table_row, np_blocks)].add(
            -1, mode="drop")
        cand, got = _rank_alloc(np_blocks, rc_row, sel)
        table_row = jnp.where(got, cand, table_row)
        rc_row = rc_row.at[jnp.where(got, cand, np_blocks)].add(
            1, mode="drop")
        failed = sel & ~got
        table_row = jnp.where(failed, old_phys, table_row)
        rc_row = rc_row.at[jnp.where(failed, old_phys, np_blocks)].add(
            1, mode="drop")
        return rc_row, table_row, got, ~jnp.any(failed)

    refcount, table, moved, ok = jax.vmap(one_layer)(
        pool.refcount, table, mask)
    # copy planes only into the fresh copies (sources stay unwritten)
    copy_table = jnp.where(moved, table, UNMAPPED)
    pool_view = scatter_view(pool.view, copy_table, view)
    return (GlobalPool(view=pool_view, refcount=refcount), table,
            jnp.all(ok))


# ---------------------------------------------------------------------------
# Preemption: spill a request's physical blocks to the host, restore later
# ---------------------------------------------------------------------------

def claim_blocks(dims: CacheDims, pool: GlobalPool, mapped: jax.Array
                 ) -> Tuple[GlobalPool, jax.Array, jax.Array]:
    """Map every True entry of ``mapped`` [L, NB] to a fresh physical block
    (lowest free physical id first, per layer).

    Returns ``(pool, table, ok)`` — ``ok`` is False when some layer's free
    list could not back the full mapping (the caller must not use the
    partial table; the engine's admission gate checks free counts first so
    this only fires on a gate bug)."""
    np_blocks = pool.refcount.shape[1]

    def one_layer(rc_row, need):
        cand, got = _rank_alloc(np_blocks, rc_row, need)
        table_row = jnp.where(got, cand, UNMAPPED)
        rc_row = rc_row.at[jnp.where(got, cand, np_blocks)].add(
            1, mode="drop")
        return rc_row, table_row, ~jnp.any(need & ~got)

    refcount, table, ok = jax.vmap(one_layer)(pool.refcount, mapped)
    return GlobalPool(view=pool.view, refcount=refcount), table, jnp.all(ok)


def extract_request(dims: CacheDims, pool: GlobalPool, table: jax.Array
                    ) -> Tuple[PoolView, jax.Array]:
    """Snapshot a request's physical blocks for a host-side spill.

    Returns the per-request paged view (``[L, NB, H, BS, ...]``, gathered
    through the table) and the ``[L, NB]`` mapped mask.  Unmapped logical
    blocks gather garbage (block 0) — harmless, because restore only
    claims and scatters the mapped entries and every slot of an unmapped
    block is FREE in the spilled metadata."""
    return gather_view(pool.view, table), table >= 0


def restore_request(dims: CacheDims, pool: GlobalPool, mapped: jax.Array,
                    view: PoolView
                    ) -> Tuple[GlobalPool, jax.Array, jax.Array]:
    """Re-admit a spilled request: claim fresh physical blocks for its
    mapped logical blocks and scatter the spilled planes back through the
    new table.  The physical ids generally differ from the pre-spill ones,
    but every read goes through the block table in LOGICAL order, so the
    resumed attention math is bit-exact."""
    pool, table, ok = claim_blocks(dims, pool, mapped)
    pool = GlobalPool(view=scatter_view(pool.view, table, view),
                      refcount=pool.refcount)
    return pool, table, ok


def check_pool_invariants(pool: GlobalPool, tables, extra_tables=()) -> dict:
    """Host-side audit of the refcounted pool accounting invariants.

    ``tables`` is ``[R, L, NB]`` (or a single ``[L, NB]``) of the LIVE
    block tables; ``extra_tables`` is an iterable of further ``[L, NB]``
    reference holders (prefix-cache entries — one per registration — and
    preempted requests' retained shared mappings).  For every layer:

    * every physical block's refcount EQUALS the number of references the
      provided holders make to it (no leaked or phantom reference — with
      sharing, a block may legitimately appear in several tables, and the
      refcount must say exactly how many);
    * no refcount is negative (no double-free);
    * ``claimed(refcount > 0) + free(refcount == 0) == pool_blocks``.

    Raises AssertionError on violation; returns per-layer counts."""
    import numpy as np
    rc = np.asarray(pool.refcount)
    tb = np.asarray(tables)
    if tb.ndim == 2:
        tb = tb[None]
    holders = [tb] + [np.asarray(t)[None] if np.asarray(t).ndim == 2
                      else np.asarray(t) for t in extra_tables]
    L, NP = rc.shape
    assert (rc >= 0).all(), \
        f"negative refcount (double-free): min {rc.min()}"
    claimed = []
    for l in range(L):
        refs = np.zeros(NP, np.int64)
        for h in holders:
            mapped = h[:, l][h[:, l] >= 0]
            np.add.at(refs, mapped, 1)
        bad = np.nonzero(refs != rc[l])[0]
        assert bad.size == 0, \
            (f"layer {l}: refcount mismatch at physical blocks "
             f"{bad.tolist()[:8]}: counted {refs[bad][:8].tolist()} refs, "
             f"pool says {rc[l][bad][:8].tolist()}")
        n_claimed = int((rc[l] > 0).sum())
        n_free = int((rc[l] == 0).sum())
        assert n_claimed + n_free == NP, \
            f"layer {l}: claimed({n_claimed}) + free({n_free}) != {NP}"
        claimed.append(n_claimed)
    return {"claimed": claimed, "free": (rc == 0).sum(axis=1).tolist(),
            "pool_blocks": NP}


def engine_advance(cfg: ThinKVConfig, dims: CacheDims, pool: GlobalPool,
                   table: jax.Array, cache: CTCache, sparsity: jax.Array,
                   active: jax.Array, n_new: jax.Array | int = 1,
                   with_alloc_fail: bool = False, track_cow: bool = True,
                   axis_name: str | None = None, policy=None):
    """Engine-side ``advance_after_write`` against the shared global pool.

    ``n_new`` tokens were written into the buffer this call (1 per decode
    tick; up to g for a prefill chunk — chunks align with group commits).
    The pool is only touched when a commit or refresh is actually due
    (every g / tau tokens) — the gather/scatter through the block table is
    cold-path maintenance, never per-token traffic.

    COPY-ON-WRITE: a commit that changes the content of a SHARED physical
    block (refcount > 1 — prefix-cached or mapped by another holder)
    never writes it in place; the dirty mask is computed by comparing the
    gathered pre-commit view against the post-commit view, and
    :func:`sync_block_tables` claims a fresh block, copies the planes,
    and decrefs the source.  The compare runs only on commit/refresh
    calls (every g / tau tokens), in the same cold path as the
    gather/scatter itself; ``track_cow=False`` (a TRACE-TIME flag)
    compiles it out entirely — sound whenever no block can be shared
    (the engine passes it when the prefix cache is disabled: every
    refcount is then 0 or 1, so the dirty mask could never matter).

    With ``with_alloc_fail=True`` two extra values are returned: a scalar
    bool, True iff this call's commit hit an allocation failure (claims
    reverted, group data dropped), and an int32 scalar counting the COW
    faults this call performed.  The serving engine threads both out of
    the jitted tick; it asserts the failure flag never fires — its
    preemption headroom checks make failure impossible by pausing victims
    before an unbackable commit (counting a committing slot's shared
    blocks as potential COW claims).

    ``policy`` (a TRACE-TIME strategy object, see ``core/policy.py``)
    selects the retention policy for commits, TBE anneals, and budget
    eviction; ``None`` is the paper's default ThinKV policy.
    """
    policy = get_policy(policy)

    def advance(args):
        pool, table, cache, _, _ = args
        cache = cache.replace(buf_len=cache.buf_len + n_new,
                              num_tokens=cache.num_tokens + n_new)
        at_commit = cache.buf_len >= dims.G
        at_refresh = (cache.num_tokens % cfg.refresh_interval) == 0

        def maintain(args):
            pool, table, cache, _, _ = args
            with jax.named_scope(TR.GATHER_VIEW):
                view0 = gather_view(pool.view, table)
            with jax.named_scope(TR.COMMIT_EVICT):
                cache, view = commit_and_evict_if_full(
                    cfg, dims, cache, view0, axis_name=axis_name,
                    policy=policy)
            with jax.named_scope(TR.REFRESH):
                cache = jax.lax.cond(
                    at_refresh,
                    lambda c: refresh(cfg, dims, c, view, sparsity,
                                      axis_name=axis_name, policy=policy),
                    lambda c: c, cache)
            with jax.named_scope(TR.SYNC_TABLES):
                if track_cow:
                    # a slot dirty in ANY shard's heads must COW on EVERY
                    # shard (the table/refcount updates are replicated)
                    dirty = _any_shard(changed_slots(view0, view),
                                       axis_name)
                else:
                    dirty = None
                pool, table, cache, failed, cow = sync_block_tables(
                    dims, pool, table, cache, view, dirty_slots=dirty)
            return (pool, table, cache, jnp.any(failed),
                    jnp.sum(cow.astype(jnp.int32)))

        return jax.lax.cond(at_commit | at_refresh, maintain, lambda a: a,
                            (pool, table, cache, jnp.bool_(False),
                             jnp.int32(0)))

    out = jax.lax.cond(active, advance, lambda a: a,
                       (pool, table, cache, jnp.bool_(False), jnp.int32(0)))
    return out if with_alloc_fail else out[:3]


# ---------------------------------------------------------------------------
# Read side: dequantize / reference attention inputs / metrics
# ---------------------------------------------------------------------------

def dequant_layer(dims: CacheDims, cache: CTCache, view: PoolView,
                  layer: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Reference read of one layer: (k, v, valid) with k/v [NS,H,D] f32."""
    kc, vc, ks, vs = (page_tokens(p[layer]) for p in view)
    bits = cache.slot_bits[layer].astype(jnp.int32)[:, None, None]
    k = Q.dequantize_by_bitcode(kc, ks.astype(jnp.float32), bits)
    v = Q.dequantize_by_bitcode(vc, vs.astype(jnp.float32), bits)
    valid = cache.slot_state[layer] == VALID
    return k, v, valid


def valid_counts(cache: CTCache) -> jax.Array:
    return jnp.sum((cache.slot_state == VALID).astype(jnp.int32), axis=1)


def memory_stats(cfg: ThinKVConfig, dims: CacheDims, cache: CTCache) -> dict:
    """Physical + effective footprint and pressure metrics."""
    used_blocks = jnp.sum((cache.block_type >= 0).astype(jnp.int32), axis=1)
    n_valid = valid_counts(cache)
    slot_bits = cache.slot_bits.astype(jnp.float32)
    eff_bits = jnp.where(cache.slot_state == VALID, slot_bits, 0.0)
    avg_bits = jnp.sum(eff_bits) / jnp.maximum(jnp.sum(
        (cache.slot_state == VALID).astype(jnp.float32)), 1.0)
    bytes_per_slot = (2 * dims.H * dims.D // (2 if dims.nibble else 1)
                      + 2 * dims.H * dims.scale_groups)  # codes + e4m3 scales
    return {
        "valid_tokens": n_valid,
        "used_blocks": used_blocks,
        "physical_bytes": used_blocks * dims.BS * bytes_per_slot,
        "avg_bits": avg_bits,
        "pressure": used_blocks / dims.NB,
    }


def metadata_bytes(dims: CacheDims) -> int:
    """Exact byte count of one request's :class:`CTCache` METADATA (every
    field except the bf16 TBQ buffer) — kept next to :func:`init_cache`
    so the accounting cannot drift from the field list, and pinned
    against live array ``nbytes`` in ``tests/test_policy.py``.

    Per layer: slot_state/bits (uint8) + slot_seg/pos (int32) per slot,
    block_type (int8) per block, seg_level (int32) per segment; shared:
    seg_type (int32) per segment + five int32 scalars."""
    per_layer = dims.NS * (1 + 4 + 4 + 1) + dims.NB + 4 * dims.S
    return dims.L * per_layer + 4 * dims.S + 5 * 4


def buffer_bytes(dims: CacheDims) -> int:
    """Exact byte count of the bf16 TBQ buffer (buf_k + buf_v)."""
    return dims.L * 2 * 2 * dims.G * dims.H * dims.D
