"""ThinKV serving engine: continuous batching + the full paper loop.

The engine owns a SHARED global block pool (``core.ct_cache.GlobalPool``):
one physical set of quantized planes in paged ``[L, NP, H, BS, ...]``
layout, with per-request per-layer block tables mapping logical CT blocks
to physical blocks.  Blocks freed by TBE eviction (or request retirement)
return to the global free list and are reused by other requests.

SINGLE-LAUNCH DECODE TICK.  The tick's attention for EVERY layer and every
request slot is one fused kernel launch (``ct_paged_attention_fused``,
grid ``(L, R, H, NB+1)``), with the fp TBQ-buffer partition folded into
the kernel's final grid step — no per-layer launches, no XLA stats merge.
To make all-layer queries available to a single launch, the tick is a
two-pass dataflow (both backends — the dataflow is backend-independent):

  1. embed each slot's current token;
  2. TRUNK scan over layers: project qkv (RoPE'd) from the running hidden
     state, write KV into the TBQ buffer plane, apply the MLP/MoE residual;
     the per-layer queries are stacked as ``[L, R, Hq, hd]``;
  3. ATTENTION, once, over the stacked queries (CT pool ∪ buffer):
       * ``backend="kernel"``   — ONE fused ``ct_paged_attention_fused``
         launch for all layers/slots (compiled on TPU, interpret on CPU);
       * ``backend="reference"``— the dense path: gather each request's
         view, dequantize the pool to fp, joint softmax per layer (the
         parity oracle — same dataflow, XLA ops);
  4. RESIDUAL scan: apply each layer's attention output projection;
  5. ``engine_advance``: group commit (TBQ quantize + CT slot reuse +
     physical block mapping) + budget eviction every g tokens, thought
     refresh + TBE every tau — pool gather/scatter happens ONLY then;
  6. sample the next token.

The two-pass form is ATTENTION-LATE: within a tick, no layer's attention
output feeds any other layer's projections — all attention residuals join
the stream only after the trunk.  This is a materially different function
from the sequential transformer block (and stronger than GPT-J-style
parallel blocks, which still propagate attention outputs across layers);
it is the price of hoisting the layer axis into one launch, since q_l of
the sequential form depends on attention l-1.  Decode-written KV
therefore comes from trunk hidden states while prefill-written KV comes
from the sequential forward (prefill and ``serve_step`` keep the
sequential arrangement).  Both backends share the dataflow, so the parity
oracle validates the KERNEL against dense math — not the tick against
the sequential model.  Attention sparsity for calibrated layers is
measured by the dense path only on ticks where some slot refreshes.

WINDOW LAYERS AND HELD EXPERTS.  A model with ``layer_kinds`` keeps its
full layers in the paged pool (``dims.L`` is their count) and its window
layers in per-slot bf16 rings (``CTCache.ring_k/ring_v``; see
``core.ct_cache.ring`` and ``docs/serving.md``).  Such a model's trunk and
prefill run layer by layer (each kind's rope; window layers write their
ring), and the tick's attention adds the XLA window attention over the
rings beside the fused kernel over the pool.  Every MoE model's tick and
prefill use the dropless held-expert layer (``layers.moe.moe_held``).  A
model without kinds and experts compiles exactly the programs it
compiled before.

Prompts do not trickle one token per tick: admission runs a CHUNKED
BATCHED PREFILL.  Prompts >= ``prefill_chunk`` (128-multiple) tokens go
through LARGE chunks whose causal intra-chunk partition runs the COMPILED
``flash_prefill`` kernel and whose frozen-pool partition runs the batched
paged kernel (chunk queries fold into the q-group axis), committing C/g
TBQ groups per chunk in order; the tail (< 128 tokens) uses chunks of g
(the intra-chunk part of a g-sized chunk is below the kernel's 128-tile
and runs the reference oracle).  g-sized chunks reproduce the
token-by-token cache evolution exactly (chunks align with group commits;
tau % g == 0 keeps refreshes on commit boundaries).  Large chunks relax
it in two standard chunked-prefill ways: intra-chunk tokens are attended
at FULL precision (the token-by-token loop would have quantized —
possibly evicted — all but the latest group), and the chunk's single
end-of-chunk sparsity value feeds every refresh that falls inside the
chunk.  Both backends share the large-chunk dataflow, so backend parity
is unaffected; the committed KV itself is quantized identically.

OVERSUBSCRIBED POOL + PREEMPTION (request lifecycle).  ThinKV's premise
is that <5% of the dense KV suffices, so the engine runs its shared
block pool OVERSUBSCRIBED: ``pool_blocks`` may be far below the dense
worst case ``max_seqs * NB``.  Three mechanisms make that safe:

  * WATERMARK ADMISSION — ``_admission_gate`` is a per-request check:
    admit while every layer's free-block count covers the request's
    budget-derived block estimate (valid tokens/layer never exceed
    ``token_budget + g``, so ~``ceil((budget+g)/BS)`` blocks — NOT the
    dense worst case of NB) plus one commit's claim per running request
    (the low watermark).  A preempted request's estimate is exact: its
    spilled mapping.
  * PREEMPT-BEFORE-COMMIT — a group commit claims at most ``ceil(g/BS)``
    fresh blocks per layer, so before any tick/prefill chunk whose
    commits the free list cannot back, the engine PAUSES victims
    (lowest priority, then most blocks held): the victim's pool blocks,
    block tables, and TBQ buffer/metadata are spilled to a host-side
    ``PreemptedState`` (numpy), its blocks released, and the request
    re-queued as PREEMPTED.  Since the check runs ahead of need and
    frees only add, in-flight commits can never hit an allocation
    failure — the tick still threads the allocation-failure flag out of
    jit and the engine asserts it stays False (no silent data loss).
  * RESUME — admission restores a preempted request bit-exactly: fresh
    physical blocks are claimed for its spilled mapping and the planes
    scattered back.  Physical ids differ, but all reads go through the
    block table in logical order, so the resumed request's logits match
    an un-preempted run exactly (asserted on both backends) — no
    recompute, no dropped tokens.

Request states: WAITING -> RUNNING -> FINISHED, with RUNNING ->
PREEMPTED -> RUNNING cycles under pool pressure (see
``serving.scheduler``).  ``run`` raises only when nothing is preemptible
AND the queue cannot progress: no running requests, the whole pool free,
and the watermark still refuses every queued request — a pool too small
for even one request, not a transient capacity state.

COPY-ON-WRITE PREFIX CACHING (``prefix_cache=True``).  The pool's free
bitmap is generalized to a per-block REFCOUNT (free ⇔ refcount 0), and a
host-side ``serving.prefix_cache.PrefixCache`` indexes fully-committed
prefill states by token chain: the block table, metadata snapshot, and
boundary logits at every commit-aligned prefill chunk boundary (plus the
end of the prompt).  The sharing/eviction/preemption interplay:

  * HIT — an admitted request whose prompt extends a cached prefix maps
    the cached physical blocks into its block table (refcount++),
    restores the metadata snapshot, and prefills ONLY the tail; an exact
    full-prompt hit performs zero prefill forwards (the entry's logits
    feed sampling directly).  The watermark admission estimate shrinks by
    the hit's block count — shared blocks need no fresh claim.
  * COW — shared blocks (refcount > 1) are content-immutable.  Any
    holder's pool mutation — group-commit slot reuse, TBE eviction
    emptying a block, thought-refresh requantization — COW-faults first:
    ``sync_block_tables`` diffs the pre/post-commit view, claims a fresh
    block for each dirty shared block, copies the planes, swaps the
    block table, and decrefs the source.  Logical frees just decref
    (free at zero).  The preemption headroom bound counts a committing
    slot's shared blocks as potential COW claims, so in-flight commits
    still can never hit allocation failure.
  * EVICTION — under watermark pressure (admission or headroom), cache
    entries decay in LRU order BEFORE any request is preempted: dropping
    a cache reference can free blocks without pausing work.  Blocks a
    running/preempted request still maps merely decref and stay live.
  * PREEMPTION — a victim spills only its PRIVATELY-owned planes
    (refcount 1); shared blocks keep the victim's reference (they free
    no memory when spilled, and their content is pinned immutable by the
    remaining holders) and are re-attached verbatim on resume, which
    claims fresh blocks only for the private mapping.  Resume stays
    bit-exact: logical read order is unchanged on both paths.  When
    retained references would PIN the pool (a block co-held by a cache
    entry and a spill has cache_refs != refcount, so decay refuses it
    and preemption retained it — each deferring to the other), the
    last-resort valve ``_demote_spilled_shared`` decrefs the retained
    references and folds them into the private spill mapping; resume
    then scatters the already-spilled planes (still bit-exact — the
    spill snapshots every mapped block) and decay can free the blocks.

TENSOR-PARALLEL SHARDING (``mesh=``).  Given a device mesh with a
``model`` axis (``launch.mesh.make_serve_mesh("model=N")``), the engine
shards its HEAVY state over the KV-HEAD axis: pool K/V planes
(``[L, NP, H, BS, ...]``), TBQ buffers (``[R, L, G, H, D]``), and the
per-layer attention — each shard launches the SAME fused
``ct_paged_attention_fused`` kernel over its H/N local heads (still one
launch per tick per shard).  Everything head-AGNOSTIC stays REPLICATED:
weights, block tables, refcounts, slot/segment metadata, the scheduler,
the prefix cache, and all host-side pool accounting — so the admission/
preemption/COW logic above runs unchanged.  The tick/prefill dataflows
are wrapped in ``shard_map``:

  * trunk + MLP + residual/unembed run replicated (identical on every
    shard); queries/KV are SLICED to the shard's contiguous kv-head
    range before the buffer write and the attention launch, and only the
    attention OUTPUT is all-gathered back into the replicated stream;
  * the two cross-head computations inside cache maintenance gather
    explicitly (see ``core.ct_cache``): TBE's kmeans keys (flattened
    over ALL heads) and the COW dirty mask (OR across shards);
  * per-head attention math, quantization groups (within one head's
    head_dim), and slot allocation are head-local or metadata-only, so
    every shard makes byte-identical metadata/refcount decisions.

Because no FLOATING-POINT reduction ever crosses shards (gathers are
data movement; the dirty-mask reduction is an integer psum), the sharded
engine is BIT-IDENTICAL to the 1-device run on both backends — asserted
end to end on CPU devices by ``tests/test_serving_traces.py``.  On four
TPU v5e chips it is not yet: the sharded and unsharded programs were
measured to differ by up to ~0.03 in the logits (``chip_smoke.py
--chips 4`` fails its token check).  Spill/resume under
sharding: ``PreemptedState`` GATHERS the shards to host numpy
(``np.asarray`` of the head-sharded planes) and resume scatters the
planes back through the freshly claimed table with the head axis
re-partitioned — preemption survives mesh-size changes (a trace spilled
on one topology could in principle resume on another).

THE API SEAM (``docs/serving.md``).  The engine itself is DEVICE-FACING
only: it owns the pool, the jitted tick/prefill programs, and the
admission/preemption/COW bookkeeping, exposed through a JetStream-style
surface —

    prefill(prompt, slot, rng) -> (Prefix, rng)   # chunked prefill +
                                                  # first-token sample
    insert(prefix, slot)       -> bool            # materialize a Prefix
    generate(rng)              -> (ResultTokens, rng)  # ONE fused tick,
                                                  # non-blocking D2H
    free_resource(slot)                           # release every pool ref
    drop_spill(arrival)                           # drop a cancelled spill

``Prefix`` reuses the ``PreemptedState`` spill format as its portable
transfer form (``detach_prefix``), so preemption resume and a
disaggregated prefill→decode handoff are the SAME code path; a
``ResultTokens`` starts its D2H copies at construction
(``copy_to_host_async``) so the transfer overlaps the next dispatch.
The HOST LOOP lives in ``serving.orchestrator``: an asyncio
continuous-batching loop with per-request ``async for`` token streams,
mid-flight cancellation, and TTFT/TPOT/queue-wait metrics.  ``run()``
is a thin synchronous wrapper over it that replays the historical
monolithic loop's decision order bit-exactly.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import FULL, WINDOW, ArchFamily, ServeConfig
from repro.core import ct_cache as CC
from repro.core.thoughts import row_sparsity
from repro.kernels import ops as K
from repro.kernels import ref as KR
from repro.layers import attention as A
from repro.layers import embedding as E
from repro.layers.common import softcap
from repro.layers.mlp import mlp
from repro.layers.moe import moe_held
from repro.layers.norms import rmsnorm
from repro.serving import sampling as SMP
from repro.serving import tracing as TR
from repro.serving.scheduler import Request, Scheduler

NEG_INF = -1e30

# drift-probe length bucket: reference replays pad prompt+output to the
# next multiple so the number of distinct compiled shapes (and hence
# probe retraces) is bounded by max_len / DRIFT_PAD, not by request count
DRIFT_PAD = 32


def _check_serveable(m, mesh, lstar) -> None:
    """Refuse, loudly, what the engine would otherwise serve as another
    model: a window without layer kinds (it would be full attention), a
    per-kind rope without kinds, window layers under the KV-head mesh
    (their rings are not sharded with the pool), a model with no full
    layer (nothing for the ThinKV pool), and L* outside the full
    layers."""
    if m.sliding_window > 0 and not m.layer_kinds:
        raise ValueError(
            f"{m.name}: sliding_window={m.sliding_window} without "
            f"layer_kinds; the engine serves a window only in window "
            f"layers, never as full attention")
    if m.rope_full and not m.layer_kinds:
        raise ValueError(f"{m.name}: a rope per layer kind needs "
                         f"layer_kinds")
    if not m.layer_kinds:
        return
    if not m.full_layers:
        raise ValueError(f"{m.name}: no full attention layer; the ThinKV "
                         f"pool needs at least one")
    if m.window_layers and mesh is not None:
        raise NotImplementedError(
            f"{m.name}: window layers under the KV-head mesh; their rings "
            f"are not sharded with the pool")
    if lstar is not None and not set(lstar) <= set(m.full_layers):
        raise ValueError(f"L* {tuple(lstar)} must be full attention "
                         f"layers {m.full_layers}")


def _sample_slots(slot_rngs, logits, temperature: float, top_p: float):
    """Sample every slot's next token from ``logits [R, V]`` with the
    per-slot stream keys ``slot_rngs [R, 2]``; returns ``(tokens [R],
    advanced keys)``.  Greedy (temperature 0) is pure argmax and leaves
    every stream untouched."""
    if temperature <= 0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), slot_rngs
    return jax.vmap(
        lambda k, lg: SMP.stream_sample(k, lg, temperature, top_p))(
            slot_rngs, logits)


def _joint_attend(q, k_pool, v_pool, valid_pool, buf_k, buf_v, buf_mask):
    """Dense joint attention over (pool ∪ buffer/chunk) with probs.

    q [T, Hq, D]; k_pool/v_pool [NS, H, D]; buf [G, H, D];
    valid_pool [NS]; buf_mask [T, G] per-query buffer visibility.
    Returns (out [T, Hq, D], probs [T, H, gq, NS+G], valid [T, NS+G]).
    """
    t, hq, hd = q.shape
    h = k_pool.shape[1]
    gq = hq // h
    k = jnp.concatenate([k_pool, buf_k.astype(k_pool.dtype)], 0)
    v = jnp.concatenate([v_pool, buf_v.astype(v_pool.dtype)], 0)
    valid = jnp.concatenate(
        [jnp.broadcast_to(valid_pool[None], (t, valid_pool.shape[0])),
         buf_mask], 1)                                       # [T, NS+G]
    qh = q.reshape(t, h, gq, hd).astype(jnp.float32)
    s = jnp.einsum("thgd,nhd->thgn", qh,
                   k.astype(jnp.float32)) / jnp.sqrt(float(hd))
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(valid[:, None, None, :], p, 0.0)
    out = jnp.einsum("thgn,nhd->thgd", p,
                     v.astype(jnp.float32)).reshape(t, hq, hd)
    return out.astype(q.dtype), p, valid


def _ring_attend(q, ring_k, ring_v, valid):
    """Window layers' decode attention over the slots' rings, in XLA.

    q [Lw, R, Hq, D]; rings [R, Lw, W, H, D] bf16; valid [R, W].  The
    products take bf16 operands and accumulate in f32 (what a float32
    product does on the TPU at default precision), so the rings are read
    as stored.  Returns [Lw, R, Hq, D] f32."""
    lw, r, hq, d = q.shape
    h = ring_k.shape[3]
    qh = q.reshape(lw, r, h, hq // h, d).astype(ring_k.dtype)
    s = jnp.einsum("lrhgd,rlwhd->lrhgw", qh, ring_k,
                   preferred_element_type=jnp.float32) / np.sqrt(d)
    s = jnp.where(valid[None, :, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("lrhgw,rlwhd->lrhgd", p.astype(ring_v.dtype), ring_v,
                   preferred_element_type=jnp.float32)
    return o.reshape(lw, r, hq, d)


def _window_chunk_attend(q, k, v, q_pos, k_pos, k_valid, window: int):
    """A prefill chunk's window attention: q [T, Hq, D] at positions
    ``q_pos`` [T] over keys k/v [N, H, D] at ``k_pos`` [N] (the ring,
    then the chunk itself), each query seeing the valid keys at
    ``q_pos - window < k_pos <= q_pos``.  Same precision as
    :func:`_ring_attend`; returns [T, Hq, D] f32."""
    t, hq, d = q.shape
    h = k.shape[1]
    qh = q.reshape(t, h, hq // h, d).astype(jnp.bfloat16)
    s = jnp.einsum("thgd,nhd->thgn", qh, k.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32) / np.sqrt(d)
    seen = k_valid[None, :] & (k_pos[None, :] <= q_pos[:, None]) & \
        (k_pos[None, :] > q_pos[:, None] - window)
    s = jnp.where(seen[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("thgn,nhd->thgd", p.astype(jnp.bfloat16),
                   v.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)
    return o.reshape(t, hq, d)


def _probs_sparsity(p_t, valid_t, axis_name=None):
    """Paper App. C.2 sparsity from one query's probs [H, gq, N].

    Per-head sparsities are head-local; the final mean runs over ALL
    heads — under head sharding (``axis_name`` set) the per-head values
    are all-gathered first so the sharded mean is bit-identical to the
    single-device one (a psum would re-order the float reduction)."""
    pooled = jnp.max(p_t, axis=1)
    pooled = jnp.where(valid_t[None, :], pooled, 0.0)
    pooled = pooled / jnp.maximum(jnp.sum(pooled, -1, keepdims=True), 1e-30)
    per_head = row_sparsity(
        pooled, jnp.broadcast_to(valid_t[None, :], pooled.shape))   # [H]
    per_head = CC.gather_heads(per_head, axis_name, axis=0)
    return jnp.mean(per_head)


@dataclasses.dataclass
class PreemptedState:
    """Host-side (numpy) spill of a paused request's device state.

    Holds everything needed for a bit-exact resume: the request's pool
    planes gathered through its block table (``view``, per-request paged
    layout), which logical blocks were mapped (``mapped`` [L, NB]), the
    full per-request cache pytree (slot/segment metadata + the fp TBQ
    buffer), and the host loop's bookkeeping (generated-token count and
    the token to feed at the next tick)."""

    view: tuple                # PoolView planes as numpy [L, NB, BS, ...]
    mapped: "np.ndarray"       # [L, NB] bool — PRIVATE blocks to respill
    cache: object              # CTCache with numpy leaves
    tokens_out: int
    next_token: int
    # physical ids of SHARED blocks (refcount > 1 at spill time) whose
    # reference the victim RETAINS while paused: spilling them frees no
    # memory, their content is pinned immutable by the other holders, and
    # resume re-attaches them verbatim ([L, NB] int32, -1 elsewhere)
    shared_table: "np.ndarray" = None
    # the request's private sampling-stream key at spill time ([2]
    # uint32) — restored verbatim so a preempted temperature>0 request
    # resumes its stream exactly where it paused (schedule-invariance:
    # preemption must not perturb the request's sampled tokens)
    rng: "np.ndarray" = None


@dataclasses.dataclass
class Prefix:
    """Transferable result of :meth:`ThinKVEngine.prefill`.

    Two forms (JetStream-style prefill/insert seam):

    * RESIDENT (``slot >= 0, state is None``) — the prefilled KV already
      lives in the engine's pool under ``slot``'s block table; ``insert``
      into the same slot only seeds the next-token feed.  This is the
      fast path the orchestrator uses (prefill ran in the admitted slot).
    * PORTABLE (``state`` set) — ``detach_prefix`` spilled the planes to
      host numpy in the :class:`PreemptedState` transfer format (the same
      one preemption uses); ``insert`` claims fresh physical blocks and
      scatters them back into ANY slot of ANY engine with matching dims —
      the disaggregated prefill/decode handoff shape.
    """

    length: int                # prompt tokens materialized in the cache
    first_token: int           # sampled from the last-prompt-token logits
    logits: "np.ndarray"       # last-token logits [V] (host)
    slot: int = -1             # resident slot, -1 once detached
    state: Optional[PreemptedState] = None


class ResultTokens:
    """Packed per-tick result with ``copy_to_host_async`` semantics.

    Wraps the device arrays one fused decode tick produced — next tokens
    [R], per-slot validity [R], generated-so-far lengths [R], last-token
    logits [R, V], plus the deferred commit-failure flag, COW-fault count
    and the tick's device counters (``{name: int32 scalar}``: the mapped
    pages its attention read, and where the model has them the window
    positions it attended and the routed pairs its held experts took,
    see :meth:`ThinKVEngine.consume`) — and starts their
    D2H copies IMMEDIATELY at construction, so the transfer overlaps
    whatever the host dispatches next (the next tick, a prefill chunk).
    Nothing blocks until :meth:`block` (or the ``*_host`` properties),
    which the orchestrator calls from an executor thread while the
    asyncio loop keeps streaming."""

    packed = False                       # one tick per result

    def __init__(self, tick: int, tokens, valid: np.ndarray,
                 lengths: np.ndarray, logits, alloc_fail, cow_faults,
                 counters):
        self.tick = tick                 # 1-based tick index of this result
        self.valid = valid               # [R] bool (host — scheduler truth)
        self.lengths = lengths           # [R] tokens generated AFTER this
        self._tokens = tokens            # [R] int32 (device)
        self._logits = logits            # [R, V] (device)
        self._alloc_fail = alloc_fail
        self._cow_faults = cow_faults
        self._counters = counters        # {name: int32 scalar} (device)
        self._host = None
        for x in (tokens, logits, alloc_fail, cow_faults,
                  *counters.values()):
            if hasattr(x, "copy_to_host_async"):
                x.copy_to_host_async()

    def block(self) -> "ResultTokens":
        """Wait for the D2H copies; host views cached idempotently."""
        if self._host is None:
            with TR.span(TR.FETCH_TOKENS):
                tokens = np.asarray(self._tokens)
                fail = bool(np.any(np.asarray(self._alloc_fail)))
                cow = np.asarray(self._cow_faults).astype(np.int64)
                counters = {k: int(np.asarray(v))
                            for k, v in self._counters.items()}
            with TR.span(TR.FETCH_LOGITS):
                logits = np.asarray(self._logits)
            self._host = (tokens, logits, fail, int(cow.sum()), cow,
                          counters)
        return self

    @property
    def tokens_host(self) -> np.ndarray:
        return self.block()._host[0]

    @property
    def logits_host(self) -> np.ndarray:
        return self.block()._host[1]

    @property
    def alloc_fail_host(self) -> bool:
        return self.block()._host[2]

    @property
    def cow_faults_host(self) -> int:
        return self.block()._host[3]

    @property
    def cow_per_slot_host(self) -> np.ndarray:
        """Per-slot COW-fault counts [R] — lets the engine attribute
        faults to forked slots (best-of-n divergence accounting)."""
        return self.block()._host[4]

    @property
    def counters_host(self) -> Dict[str, int]:
        """The tick's device counters (``attn_pages``: mapped pool pages
        its attention read through the tables, the pages the fused
        kernel fetches; see :meth:`ThinKVEngine.consume`)."""
        return self.block()._host[5]


class MultiResultTokens:
    """Packed MULTI-tick result of one mega-dispatch (``packed=True``).

    One ``generate`` call fused up to ``requested`` decode ticks in a
    single ``lax.while_loop`` launch; this wraps everything the loop
    produced — per-trip tokens ``[N, R]``, per-trip slot validity
    ``[N, R]`` (a slot that finished via EOS/length inside the pack is
    invalid from the NEXT trip on), per-trip logits ``[N, R, V]``, the
    per-slot COW-fault counts, the OR'd allocation-failure flag, the
    device counters summed over its trips, and the trip count the loop
    actually executed (``trips_host < requested``
    means a scheduling event — a slot finishing — exited the loop
    early).  Rows ``trips_host..N-1`` of every buffer are zero-filled
    and must be ignored.

    Same ``copy_to_host_async`` contract as :class:`ResultTokens`:
    D2H copies start at construction, nothing blocks until
    :meth:`block` / the ``*_host`` properties.  The orchestrator drains
    the pack trip by trip (fan-out order identical to ``trips`` separate
    single-tick results); ``consume`` folds trip counts into
    ``metrics["ticks"]`` and the host token mirror — host bookkeeping
    is deferred until the pack lands, since the host cannot know the
    executed trip count at dispatch time."""

    packed = True

    def __init__(self, base_tick: int, requested: int, tokens, valid,
                 logits, alloc_fail, cow_faults, trips, counters):
        self.base_tick = base_tick       # metrics["ticks"] at dispatch
        self.tick = base_tick + 1        # first fused tick (dispatch log)
        self.requested = requested       # host-precomputed safe trip cap
        self._tokens = tokens            # [N, R] int32 (device)
        self._valid = valid              # [N, R] bool (device)
        self._logits = logits            # [N, R, V] (device)
        self._alloc_fail = alloc_fail
        self._cow_faults = cow_faults    # [R] per-slot (device)
        self._trips = trips              # int32 scalar (device)
        self._counters = counters        # {name: int32}, summed over trips
        self._host = None
        for x in (tokens, valid, logits, alloc_fail, cow_faults, trips,
                  *counters.values()):
            if hasattr(x, "copy_to_host_async"):
                x.copy_to_host_async()

    def block(self) -> "MultiResultTokens":
        """Wait for the D2H copies; host views cached idempotently."""
        if self._host is None:
            with TR.span(TR.FETCH_TOKENS):
                tokens = np.asarray(self._tokens)
                valid = np.asarray(self._valid)
                fail = bool(np.any(np.asarray(self._alloc_fail)))
                cow = np.asarray(self._cow_faults).astype(np.int64)
                trips = int(np.asarray(self._trips))
                counters = {k: int(np.asarray(v))
                            for k, v in self._counters.items()}
            with TR.span(TR.FETCH_LOGITS):
                logits = np.asarray(self._logits)
            self._host = (tokens, valid, logits, fail, cow, trips, counters)
        return self

    @property
    def tokens_host(self) -> np.ndarray:
        return self.block()._host[0]

    @property
    def valid_host(self) -> np.ndarray:
        return self.block()._host[1]

    @property
    def logits_host(self) -> np.ndarray:
        return self.block()._host[2]

    @property
    def alloc_fail_host(self) -> bool:
        return self.block()._host[3]

    @property
    def cow_per_slot_host(self) -> np.ndarray:
        return self.block()._host[4]

    @property
    def cow_faults_host(self) -> int:
        return int(self.block()._host[4].sum())

    @property
    def trips_host(self) -> int:
        return self.block()._host[5]

    @property
    def counters_host(self) -> Dict[str, int]:
        return self.block()._host[6]


class ThinKVEngine:
    """Decoder-only LM serving with ThinKV (dense / MoE / VLM backbones).

    ``backend``:
      * ``"kernel"``    — paged-attention kernel decode path (compiled on
        TPU, interpret mode elsewhere);
      * ``"reference"`` — dense-dequant XLA path (parity oracle);
      * ``"auto"``      — kernel on TPU, reference on CPU.
    """

    def __init__(self, cfg: ServeConfig, params=None,
                 lstar: Optional[Sequence[int]] = None,
                 backend: str = "auto", pool_blocks: Optional[int] = None,
                 record_logits: bool = False,
                 prefill_chunk: Optional[int] = None,
                 prefix_cache: bool = False,
                 prefix_cache_capacity: int = 64,
                 ticks_per_dispatch: int = 1,
                 allow_forks: bool = False,
                 mesh=None,
                 policy=None,
                 drift_probe: bool = False):
        assert cfg.model.family in (ArchFamily.DENSE, ArchFamily.MOE,
                                    ArchFamily.VLM), \
            "engine demo covers decoder-only backbones (the paper's scope)"
        assert cfg.thinkv.refresh_interval % cfg.thinkv.group_size == 0, \
            "chunked prefill needs tau % g == 0 (refreshes on commits)"
        if backend == "auto":
            backend = "kernel" if jax.default_backend() == "tpu" \
                else "reference"
        assert backend in ("kernel", "reference"), backend
        self.backend = backend
        # interpret-mode kernels off-TPU; compiled on TPU
        self._force = None if jax.default_backend() == "tpu" else "pallas"
        self.cfg = cfg
        self.mcfg = cfg.model
        self.tk = cfg.thinkv
        # retention policy: a TRACE-TIME strategy object (name or
        # instance; see core/policy.py + docs/policy.md) captured in the
        # jit closures below — two engines with different policies are
        # two different compiled programs.  The default resolves to the
        # paper's ThinKVPolicy and compiles bit-identically to the
        # pre-policy-interface engine.
        from repro.core.policy import get_policy
        self.policy = get_policy(policy)
        self.policy.validate(cfg.thinkv)
        _check_serveable(cfg.model, mesh, lstar)
        from repro.models import build_model
        self.model = build_model(cfg.model)
        self.params = params if params is not None \
            else self.model.init_params(cfg.seed)
        # layer kinds: full layers keep their keys and values in the paged
        # pool, window layers in per-slot rings (``ct_cache.layer_map``);
        # without kinds every layer is full and nothing below changes
        full = cfg.model.full_layers
        self._kinds = cfg.model.layer_kinds or None
        self._layer_index = CC.layer_map(self._kinds) if self._kinds \
            else None
        self.dims = CC.make_dims(self.tk, len(full),
                                 cfg.model.num_kv_heads, cfg.model.head_dim,
                                 window_layers=len(cfg.model.window_layers),
                                 window=cfg.model.sliding_window)
        # --- tensor-parallel sharding over the KV-head axis (see module
        # docstring): pool planes / TBQ buffers / attention sharded over
        # mesh["model"], everything head-agnostic replicated ---
        self.mesh = mesh
        if mesh is not None:
            from repro.distributed import sharding as SH
            n = SH._axis_sizes(mesh).get(SH.SERVE_HEAD_AXIS, 1)
            assert SH.head_shardable(self.dims.H, mesh), \
                (f"mesh['{SH.SERVE_HEAD_AXIS}']={n} cannot shard "
                 f"{self.dims.H} kv heads (head sharding needs "
                 f"kv_heads % mesh size == 0)")
            self._nshard, self._axis = n, SH.SERVE_HEAD_AXIS
        else:
            self._nshard, self._axis = 1, None
        # L* (the sparsity probe's layers) as pool layers: full layers only
        n_lstar = min(self.tk.num_calib_layers, len(full))
        self.lstar = tuple(full.index(int(x)) for x in (
            lstar if lstar is not None else full[:n_lstar]))
        self.scheduler = Scheduler(cfg.max_seqs)
        self.num_pool_blocks = pool_blocks if pool_blocks is not None \
            else cfg.max_seqs * self.dims.NB
        self.pool = CC.init_global_pool(self.dims, self.num_pool_blocks)
        self.tables = jnp.broadcast_to(
            CC.init_block_table(self.dims)[None],
            (cfg.max_seqs, self.dims.L, self.dims.NB)).copy()
        self.caches = jax.vmap(lambda _: CC.init_cache(self.dims))(
            jnp.arange(cfg.max_seqs))
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            self.params = jax.device_put(
                self.params, NamedSharding(self.mesh, PartitionSpec()))
            self._place_state()
        if prefill_chunk is None:
            # default: 128-token large chunks when they can align with
            # group commits; a g that does not divide 128 disables the
            # large-chunk path (g-sized chunks only) rather than failing
            prefill_chunk = 128 if 128 % self.dims.G == 0 else 0
        assert prefill_chunk == 0 or (prefill_chunk % 128 == 0 and
                                      prefill_chunk % self.dims.G == 0), \
            "large prefill chunks must be 128-multiples aligned with commits"
        self.prefill_chunk = prefill_chunk
        # trace-time flag: without the prefix cache OR forked generation
        # no block is ever shared (refcounts stay 0/1), so the COW
        # content diff in engine_advance is compiled out of the
        # tick/prefill entirely.  ``allow_forks`` opts into sharing via
        # ``fork_slot`` (samples_per_slot) with the cache off.
        self._track_cow = bool(prefix_cache) or bool(allow_forks)
        assert int(ticks_per_dispatch) >= 1, ticks_per_dispatch
        self.ticks_per_dispatch = int(ticks_per_dispatch)
        # unjitted fns kept for jaxpr inspection (launch-count auditing)
        self._tick_fn = self._make_tick()
        self._tick = jax.jit(self._tick_fn)
        self._megatick_fn = self._make_megatick() \
            if self.ticks_per_dispatch > 1 else None
        self._megatick = jax.jit(self._megatick_fn) \
            if self._megatick_fn is not None else None
        self._prefill_chunk_fn = self._make_prefill_chunk()
        self._prefill_chunk = jax.jit(self._prefill_chunk_fn)
        self._prefill_big_fn = self._make_prefill_big() if prefill_chunk \
            else None
        self._prefill_big = jax.jit(self._prefill_big_fn) if prefill_chunk \
            else None
        self._reset_slot = jax.jit(self._make_reset())
        # logit-drift probe: replays each finished request through the
        # UNCOMPRESSED dense forward and compares against the logits the
        # compressed serving path actually produced (needs them recorded)
        self.drift_probe = bool(drift_probe)
        if self.drift_probe:
            record_logits = True
            self._drift_probe_fn = self._make_drift_probe()
            self._drift_probe_jit = jax.jit(self._drift_probe_fn)
        else:
            self._drift_probe_fn = None
            self._drift_probe_jit = None
        self.record_logits = record_logits
        self.trace: List[Dict] = []          # per-call logits (for parity)
        # per-request logits sequences keyed by arrival stamp (parity tests
        # compare these across engines regardless of preemption schedule)
        self.request_logits: Dict[int, List[np.ndarray]] = {}
        self.metrics: Dict[str, float] = {"ticks": 0, "tokens": 0,
                                          "dispatches": 0,
                                          "prefill_tokens": 0,
                                          "prefill_chunks": 0,
                                          "prefill_big_chunks": 0,
                                          "preemptions": 0, "resumes": 0,
                                          "admissions": 0,
                                          "queue_wait_ticks": 0,
                                          "prefix_hits": 0,
                                          "prefix_tokens_skipped": 0,
                                          "cow_faults": 0,
                                          "forks": 0,
                                          "fork_cow_faults": 0,
                                          "peak_refcount": 0,
                                          "early_exit_finish": 0,
                                          "early_exit_headroom": 0,
                                          "cancellations": 0,
                                          "drift_probes": 0,
                                          "drift_max_abs": 0.0,
                                          "host_syncs": 0,
                                          "attn_pages": 0,
                                          "attn_page_slots": 0}
        self.metrics.update(dict.fromkeys(self._counter_names(), 0))
        if cfg.model.moe is not None:
            self.metrics["expert_slots"] = 0
        from repro.serving.prefix_cache import PrefixCache
        self.prefix_cache = PrefixCache(
            self.dims, capacity=prefix_cache_capacity) \
            if prefix_cache else None
        # --- oversubscription / preemption bookkeeping (host side) ---
        self._spilled: Dict[int, PreemptedState] = {}   # arrival -> spill
        self._queued_at: Dict[int, int] = {}            # arrival -> tick
        self._slot_ntok = np.zeros(cfg.max_seqs, np.int64)  # num_tokens mirror
        self._feed = np.zeros(cfg.max_seqs, np.int32)   # next-token inputs
        # per-slot sampling stream keys [R, 2] — reseeded from request
        # identity (fold_in(seed, arrival)) at prefill/fork time, so
        # temperature>0 sampling is schedule-invariant (see
        # ``serving.sampling``); placeholder split until then
        self._slot_rng = jax.random.split(
            jax.random.PRNGKey(cfg.seed), cfg.max_seqs)
        # slots whose blocks may be shared through ``fork_slot`` (COW
        # faults on these slots are best-of-n divergence, not prefix-
        # cache traffic — metered separately as fork_cow_faults)
        self._forked = np.zeros(cfg.max_seqs, bool)
        # worst-case fresh physical blocks one group commit can claim per
        # layer: G slots span at most ceil(G/BS) fully-free blocks
        self._cc = -(-self.dims.G // self.dims.BS)

    # ------------------------------------------------------------------
    # tensor-parallel plumbing (no-ops when mesh is None)
    # ------------------------------------------------------------------

    def _place_state(self) -> None:
        """(Re)partition the device state onto the mesh: pool planes +
        TBQ buffers sharded on the KV-head axis, everything else
        replicated.  Called at init and after a resume scatters spilled
        numpy planes back into ``self.pool``.  (A prefix-cache hit also
        rebuilds table/cache from host numpy, but only into LOCALS that
        immediately flow through the shard_map'd prefill, whose in_specs
        re-partition them — ``self`` state is untouched until the chunk
        returns properly sharded outputs.)"""
        if self.mesh is None:
            return
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.distributed import sharding as SH
        self.pool = jax.device_put(
            self.pool,
            SH.to_shardings(SH.serve_pool_specs(self.pool), self.mesh))
        self.caches = jax.device_put(
            self.caches,
            SH.to_shardings(SH.serve_cache_specs(self.caches, batched=True),
                            self.mesh))
        self.tables = jax.device_put(
            self.tables, NamedSharding(self.mesh, PartitionSpec()))

    def _local_heads(self, x, axis: int):
        """This shard's contiguous slice of a head axis (kv heads, or
        query heads — kv-head-major, so the slice is the shard's kv
        groups).  Identity off-mesh."""
        if self._axis is None:
            return x
        return K.local_heads(x, axis, self._axis, self._nshard)

    def _gather_heads(self, x, axis: int):
        """All-gather a per-shard head slice back to the full head axis
        (the only way shard-local attention rejoins the replicated
        residual stream).  Identity off-mesh."""
        return CC.gather_heads(x, self._axis, axis=axis)

    def _spmd_specs(self, single_request: bool):
        """(pool_spec, cache_spec, replicated) PartitionSpec pytrees for
        wrapping a tick/prefill dataflow in shard_map."""
        from jax.sharding import PartitionSpec as P
        from repro.distributed import sharding as SH
        return (SH.serve_pool_specs(self.pool),
                SH.serve_cache_specs(self.caches,
                                     batched=not single_request),
                P())

    def _wrap_spmd(self, fn, in_specs, out_specs):
        """shard_map a tick/prefill dataflow over the mesh (identity
        off-mesh).  ``check_vma=False``: replicated outputs are computed
        identically on every shard by construction (replicated inputs +
        deterministic ops + explicit gathers), which the static
        replication checker cannot see through collectives."""
        if self.mesh is None:
            return fn
        return jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    # ------------------------------------------------------------------
    # attention helpers shared by tick + prefill
    # ------------------------------------------------------------------

    def _dense_layer(self, q, kc_l, vc_l, ks_l, vs_l, state_l, bits_l,
                     table_l, buf_k, buf_v, buf_mask):
        """Reference path for ONE slot, one layer: gather the request's
        view through its table, dense-dequant, joint softmax with probs.

        q [T, Hq, D]; planes [NP, H, BS, ...]; state/bits [NS]; table [NB].
        """
        safe = jnp.maximum(table_l, 0)
        flat = lambda a: CC.page_tokens(a[safe])
        bits = bits_l.astype(jnp.int32)[:, None, None]
        from repro.core import quantization as Q
        kd = Q.dequantize_by_bitcode(flat(kc_l),
                                     flat(ks_l).astype(jnp.float32), bits)
        vd = Q.dequantize_by_bitcode(flat(vc_l),
                                     flat(vs_l).astype(jnp.float32), bits)
        valid = state_l == CC.VALID
        return _joint_attend(q, kd, vd, valid, buf_k, buf_v, buf_mask)

    # ------------------------------------------------------------------
    def _make_tick_core(self):
        """The UNWRAPPED single-tick dataflow (embed → trunk → fused
        attention → residual → ``engine_advance``), ending at the
        next-token logits — NO sampling, NO shard_map.  Shared verbatim
        by the single-tick program (:meth:`_make_tick`) and every trip
        of the multi-tick mega-dispatch (:meth:`_make_megatick`), which
        is what makes the two dispatch granularities bit-identical: they
        trace the exact same per-tick computation."""
        cfg, tk, dims = self.mcfg, self.tk, self.dims
        lstar = self.lstar                   # static tuple of layer ids
        lstar_arr = jnp.asarray(self.lstar)
        backend = self.backend
        R = self.cfg.max_seqs
        gq = cfg.num_heads // dims.H
        ax = self._axis                      # None off-mesh
        H_loc = dims.H // self._nshard       # kv heads per shard
        Hq_loc = cfg.num_heads // self._nshard
        full_idx = np.asarray(cfg.full_layers)
        win_idx = np.asarray(cfg.window_layers)
        # model layer -> its row of concat([pool layers, window layers])
        by_kind = np.argsort(np.concatenate([full_idx, win_idx]))

        def tick_core(params, pool, tables, caches, tokens, active):
            # every phase runs under a named scope (``serving.tracing``),
            # so the device trace can attribute its operations
            with TR.scope(TR.TICK_CORE):
                return phases(params, pool, tables, caches, tokens, active)

        def phases(params, pool, tables, caches, tokens, active):
            pos = caches.num_tokens                              # [R]
            buf_len = caches.buf_len                             # [R]
            # slots whose refresh fires in THIS tick's engine_advance
            refresh_due = active & \
                ((caches.num_tokens + 1) % tk.refresh_interval == 0)
            counters = {}

            # ---- pass 1: qkv projections + buffer write + MLP trunk ----
            def trunk(carry, inp):
                h, buf_k, buf_v = carry
                lidx, lp = inp
                x1 = rmsnorm(lp["norm1"], h, cfg.norm_eps)
                q, k, v = jax.vmap(
                    lambda xx, pp: A.qkv_decode(lp["attn"], xx, cfg, pp))(
                        x1, pos)                                 # [R,Hq,hd]

                def upd(b_r, val_r, bl):
                    row = jax.lax.dynamic_update_index_in_dim(
                        b_r[lidx], val_r.astype(b_r.dtype), bl, 0)
                    return b_r.at[lidx].set(row)
                # buffers are head-sharded: write this shard's kv heads
                buf_k = jax.vmap(upd)(buf_k, self._local_heads(k, 1),
                                      buf_len)
                buf_v = jax.vmap(upd)(buf_v, self._local_heads(v, 1),
                                      buf_len)
                x2 = rmsnorm(lp["norm2"], h, cfg.norm_eps)
                m, routed = self._ffn(lp, x2, active)
                return (h + m, buf_k, buf_v), (q, routed)

            def trunk_by_kind(h, bufs):
                """The trunk of a model with layer kinds, layer by layer:
                a window layer writes its key and value into the slot's
                ring at ``pos % W``, a full layer into the TBQ buffer;
                each applies its kind's rope."""
                buf_k, buf_v, ring_k, ring_v = bufs
                rows = jnp.arange(R)
                qs, routed = [], []
                for l, kind in enumerate(self._kinds):
                    lp = jax.tree.map(lambda x: x[l], params["layers"])
                    i = self._layer_index[l]
                    x1 = rmsnorm(lp["norm1"], h, cfg.norm_eps)
                    q, k, v = A.qkv_rope(lp["attn"], x1, cfg, pos, kind)
                    if kind == WINDOW:
                        at = pos % dims.W
                        ring_k = ring_k.at[rows, i, at].set(
                            k.astype(ring_k.dtype))
                        ring_v = ring_v.at[rows, i, at].set(
                            v.astype(ring_v.dtype))
                    else:
                        buf_k = buf_k.at[rows, i, buf_len].set(
                            k.astype(buf_k.dtype))
                        buf_v = buf_v.at[rows, i, buf_len].set(
                            v.astype(buf_v.dtype))
                    m, r = self._ffn(
                        lp, rmsnorm(lp["norm2"], h, cfg.norm_eps), active)
                    h = h + m
                    qs.append(q)
                    routed.append(r)
                return h, (buf_k, buf_v, ring_k, ring_v), jnp.stack(qs), \
                    jax.tree.map(lambda *a: jnp.stack(a), *routed)

            with TR.scope(TR.TRUNK):
                h = jax.vmap(lambda t: E.embed(params["embed"], t[None],
                                               cfg)[0])(tokens)  # [R, Dm]
                if self._kinds is None:
                    (h, buf_k, buf_v), (qs, routed) = jax.lax.scan(
                        trunk, (h, caches.buf_k, caches.buf_v),
                        (jnp.arange(cfg.num_layers), params["layers"]))
                else:
                    h, (buf_k, buf_v, ring_k, ring_v), qs, routed = \
                        trunk_by_kind(h, (caches.buf_k, caches.buf_v,
                                          caches.ring_k, caches.ring_v))
                    caches = caches.replace(ring_k=ring_k, ring_v=ring_v)
            caches = caches.replace(buf_k=buf_k, buf_v=buf_v)
            if cfg.moe is not None:
                counters["expert_assignments"] = jnp.sum(routed[0])
                counters["experts_hit"] = jnp.sum(routed[1])
            n_buf = buf_len + 1                                  # [R]
            # mapped pool pages the attention reads: the fused kernel
            # fetches these and skips the unmapped (-1) table entries
            counters["attn_pages"] = jnp.sum(tables >= 0, dtype=jnp.int32)
            # the pool layers' queries (all layers without kinds)
            qs_pool = qs if self._kinds is None else qs[full_idx]
            # queries of this shard's kv heads ([L, R, Hq/N, hd]; the Hq
            # axis is kv-head-major, so the slice is contiguous)
            qs_loc = self._local_heads(qs_pool, 2)

            def dense_one_layer(kc_l, vc_l, ks_l, vs_l, q_l, st_l, bt_l,
                                tb_l, bk_l, bv_l):
                """Dense-dequant attention + probs, one layer's planes,
                every slot — shared by the reference attention scan and
                the kernel backend's sparsity probe.  Runs on this
                shard's heads; sparsity means over ALL heads (gather
                inside :func:`_probs_sparsity`)."""
                def one(q_r, st_r, bt_r, tb_r, bk_r, bv_r, nb_r):
                    bm = (jnp.arange(dims.G) < nb_r)[None]       # [1, G]
                    o, p, valid = self._dense_layer(
                        q_r[None], kc_l, vc_l, ks_l, vs_l, st_r, bt_r,
                        tb_r, bk_r, bv_r, bm)
                    return o[0], _probs_sparsity(p[0], valid[0], ax)
                return jax.vmap(one)(q_l, st_l, bt_l, tb_l, bk_l, bv_l,
                                     n_buf)

            def dense_layer_all_slots(l):
                """:func:`dense_one_layer` at STATIC layer index l."""
                return dense_one_layer(
                    pool.view.k_codes[l], pool.view.v_codes[l],
                    pool.view.k_scales[l], pool.view.v_scales[l],
                    qs_loc[l], caches.slot_state[:, l],
                    caches.slot_bits[:, l],
                    tables[:, l], buf_k[:, l], buf_v[:, l])
            # ---- pass 2: attention, ONCE, over the stacked queries ----
            if backend == "kernel":
                with TR.scope(TR.ATTENTION):
                    qh = qs_loc.reshape(dims.L, R, H_loc, gq,
                                        cfg.head_dim).astype(jnp.float32)
                    o_all = K.paged_decode_attention_fused(
                        qh, pool.view.k_codes, pool.view.v_codes,
                        pool.view.k_scales, pool.view.v_scales,
                        CC.stacked_slot_plane(dims, caches.slot_state),
                        CC.stacked_slot_plane(dims, caches.slot_bits),
                        tables, CC.stacked_buffers(buf_k),
                        CC.stacked_buffers(buf_v), n_buf, force=self._force)
                    o_all = o_all.reshape(dims.L, R, Hq_loc,
                                          cfg.head_dim).astype(qs.dtype)
                # sparsity is only CONSUMED at tau refresh boundaries — run
                # the dense probs pass for the calibrated layers only on
                # ticks where some slot is about to refresh, keeping the
                # kernel path free of per-token dense-dequant traffic
                with TR.scope(TR.PROBE):
                    spars_calib = jax.lax.cond(
                        jnp.any(refresh_due),
                        lambda: jnp.stack([dense_layer_all_slots(l)[1]
                                           for l in lstar]),
                        lambda: jnp.zeros((len(lstar), R), jnp.float32))
                    sparsity = jnp.mean(spars_calib, axis=0)     # [R]
            else:
                def attend(_, inp):
                    (q_l, kc_l, vc_l, ks_l, vs_l, st_l, bt_l, tb_l, bk_l,
                     bv_l) = inp
                    return 0, dense_one_layer(kc_l, vc_l, ks_l, vs_l, q_l,
                                              st_l, bt_l, tb_l, bk_l, bv_l)

                with TR.scope(TR.ATTENTION):
                    _, (o_all, spars_all) = jax.lax.scan(
                        attend, 0,
                        (qs_loc, pool.view.k_codes, pool.view.v_codes,
                         pool.view.k_scales, pool.view.v_scales,
                         jnp.swapaxes(caches.slot_state, 0, 1),
                         jnp.swapaxes(caches.slot_bits, 0, 1),
                         jnp.swapaxes(tables, 0, 1),
                         jnp.swapaxes(buf_k, 0, 1),
                         jnp.swapaxes(buf_v, 0, 1)))
                    sparsity = jnp.mean(spars_all[lstar_arr], axis=0)

            if dims.LW:
                # window layers: every slot's ring, masked to the
                # positions written (the current token's included)
                n_ring = jnp.minimum(pos + 1, dims.W)
                with TR.scope(TR.WINDOW_ATTENTION):
                    o_win = _ring_attend(qs[win_idx], caches.ring_k,
                                         caches.ring_v,
                                         CC.ring_valid(dims, pos + 1))
                counters["window_positions"] = dims.LW * jnp.sum(
                    jnp.where(active, n_ring, 0), dtype=jnp.int32)
                o_all = jnp.concatenate(
                    [o_all, o_win.astype(o_all.dtype)])[by_kind]

            # ---- pass 3: attention output residuals ----
            def residual(hc, inp):
                lp, o_l = inp
                return hc + A.out_proj(lp["attn"], o_l), None

            with TR.scope(TR.RESIDUAL):
                # shard-local attention rejoins the replicated stream
                # here: all-gather the head axis, then the output
                # projection + residual run replicated (bit-identical to
                # 1-device)
                o_all = self._gather_heads(o_all, 2)
                h, _ = jax.lax.scan(residual, h, (params["layers"], o_all))

            # cache maintenance against the shared pool: sequential over
            # slots (disjoint physical blocks; allocation is serialized).
            # alloc_fail is threaded out so the host can assert the
            # preemption headroom guarantee held (it must stay all-False)
            def adv(pool, xs):
                cache_r, table_r, spars_r, active_r = xs
                pool, table_r, cache_r, fail_r, cow_r = CC.engine_advance(
                    tk, dims, pool, table_r, cache_r, spars_r, active_r,
                    with_alloc_fail=True, track_cow=self._track_cow,
                    axis_name=ax, policy=self.policy)
                return pool, (table_r, cache_r, fail_r, cow_r)

            with TR.scope(TR.ADVANCE):
                pool, (tables_out, caches, alloc_fail, cow_faults) = \
                    jax.lax.scan(adv, pool,
                                 (caches, tables, sparsity, active))

            with TR.scope(TR.UNEMBED):
                h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
                logits = softcap(E.unembed(params["embed"], h, cfg),
                                 cfg.logit_softcap)              # [R, V]
            return (pool, tables_out, caches, sparsity, logits,
                    alloc_fail, cow_faults, counters)

        return tick_core

    def _make_tick(self):
        """ONE decode tick + on-device sampling (the N=1 dispatch path):
        the shared core followed by :func:`_sample_slots` over the
        per-slot stream keys.  Greedy output is bit-identical to the
        pre-sampling-refactor tick — the core computation is unchanged
        and argmax ties break the same way."""
        core = self._make_tick_core()
        temp, top_p = self.cfg.temperature, self.cfg.top_p

        def tick(params, pool, tables, caches, tokens, active, slot_rngs):
            (pool, tables_out, caches, sparsity, logits, alloc_fail,
             cow_faults, counters) = core(params, pool, tables, caches,
                                          tokens, active)
            with TR.scope(TR.SAMPLE):
                nxt, slot_rngs = _sample_slots(slot_rngs, logits, temp,
                                               top_p)
            return (nxt, pool, tables_out, caches, sparsity, logits,
                    alloc_fail, cow_faults, slot_rngs, counters)

        pool_s, cache_s, rep = self._spmd_specs(single_request=False)
        return self._wrap_spmd(
            tick,
            in_specs=(rep, pool_s, rep, cache_s, rep, rep, rep),
            out_specs=(rep, pool_s, rep, cache_s, rep, rep, rep, rep, rep,
                       rep))

    def _make_megatick(self):
        """Fuse up to ``ticks_per_dispatch`` decode ticks in ONE
        ``lax.while_loop`` dispatch: each trip runs the shared tick core,
        samples on-device (per-slot stream keys), and feeds the sampled
        tokens straight back into the next trip's embedding — no token
        ever visits the host inside the pack.

        The loop exits only at SCHEDULING EVENTS, mirroring exactly the
        decisions the host loop would take between single ticks:

        * ``trips`` (operand) — the host-precomputed claim-safe trip
          count (:meth:`_safe_decode_trips`, from the PR 3 watermark
          machinery) capped at ``ticks_per_dispatch``; commit-claim
          headroom or preemption pressure shows up as a smaller cap;
        * a slot FINISHING — a sampled token equal to the slot's eos id,
          or the slot reaching its ``remaining`` token allowance
          (max_new_tokens), deactivates the slot and stops the loop
          after that trip so the host can retire it and admit new work.

        Slots finishing on the same trip all deactivate together; their
        later-trip rows are invalid.  The per-trip active masks, trip
        count, OR'd alloc-fail flag and per-slot COW totals come back
        packed (:class:`MultiResultTokens`)."""
        core = self._make_tick_core()
        temp, top_p = self.cfg.temperature, self.cfg.top_p
        N = self.ticks_per_dispatch
        R = self.cfg.max_seqs
        V = self.mcfg.vocab_size

        def mega(params, pool, tables, caches, tokens, active, slot_rngs,
                 remaining, eos, trips):

            def cond(c):
                t, active, stop = c[0], c[5], c[12]
                return (t < trips) & jnp.any(active) & ~stop

            def body(c):
                (t, pool, tables, caches, tokens, active, slot_rngs,
                 produced, toks, valid, logits_buf, fail, _stop, cow,
                 counters) = c
                (pool, tables, caches, _, logits, fail_t, cow_t,
                 counters_t) = core(params, pool, tables, caches, tokens,
                                    active)
                with TR.scope(TR.SAMPLE):
                    nxt, slot_rngs = _sample_slots(slot_rngs, logits, temp,
                                                   top_p)
                toks = toks.at[t].set(nxt)
                valid = valid.at[t].set(active)
                logits_buf = logits_buf.at[t].set(logits)
                produced = produced + active.astype(jnp.int32)
                done = active & ((produced >= remaining) |
                                 ((eos >= 0) & (nxt == eos)))
                return (t + 1, pool, tables, caches, nxt, active & ~done,
                        slot_rngs, produced, toks, valid, logits_buf,
                        fail | jnp.any(fail_t), jnp.any(done),
                        cow + cow_t.astype(jnp.int32),
                        jax.tree.map(jnp.add, counters, counters_t))

            init = (jnp.int32(0), pool, tables, caches, tokens, active,
                    slot_rngs, jnp.zeros(R, jnp.int32),
                    jnp.zeros((N, R), jnp.int32),
                    jnp.zeros((N, R), bool),
                    jnp.zeros((N, R, V), jnp.float32),
                    jnp.bool_(False), jnp.bool_(False),
                    jnp.zeros(R, jnp.int32),
                    {k: jnp.int32(0) for k in self._counter_names()})
            (t, pool, tables, caches, _, _, slot_rngs, _, toks, valid,
             logits_buf, fail, _, cow, counters) = jax.lax.while_loop(
                 cond, body, init)
            return (toks, valid, logits_buf, pool, tables, caches,
                    slot_rngs, t, fail, cow, counters)

        pool_s, cache_s, rep = self._spmd_specs(single_request=False)
        return self._wrap_spmd(
            mega,
            in_specs=(rep, pool_s, rep, cache_s, rep, rep, rep, rep, rep,
                      rep),
            out_specs=(rep, rep, rep, pool_s, rep, cache_s, rep, rep, rep,
                       rep, rep))

    def _counter_names(self) -> tuple:
        """The tick's device counters (:meth:`consume` folds each into
        ``metrics``)."""
        names = ("attn_pages",)
        if self.dims.LW:
            names += ("window_positions",)
        if self.mcfg.moe is not None:
            names += ("expert_assignments", "experts_hit")
        return names

    # ------------------------------------------------------------------
    def _make_prefill_chunk(self):
        cfg, tk, dims = self.mcfg, self.tk, self.dims
        lstar = jnp.asarray(self.lstar)
        backend = self.backend
        C = dims.G                      # chunk == quantization group
        ax = self._axis

        def chunk_step(params, pool, table, cache, tokens_c, n_valid):
            """Process up to C prompt tokens of ONE slot in a single
            forward (buffer starts empty: chunks align with commits)."""
            start = cache.num_tokens
            positions = start + jnp.arange(C, dtype=jnp.int32)
            tok_valid = jnp.arange(C) < n_valid
            refresh_due = ((start + n_valid) % tk.refresh_interval) == 0
            h = E.embed(params["embed"], tokens_c, cfg)          # [C, Dm]

            def body(carry, inp, kind=None):
                """One full layer (``lidx`` its pool layer; ``kind`` set
                in a model with layer kinds)."""
                h, buf_k, buf_v = carry
                lidx, lp, kc_l, vc_l, ks_l, vs_l = inp
                x1 = rmsnorm(lp["norm1"], h, cfg.norm_eps)
                if kind is not None or \
                        cfg.position_embedding.value == "rope":
                    q, k, v = A.qkv_rope(lp["attn"], x1, cfg, positions,
                                         kind or FULL)
                else:
                    q, k, v = A._project_qkv(lp["attn"], x1, cfg)
                km = jnp.where(tok_valid[:, None, None],
                               k, 0.0).astype(buf_k.dtype)
                vm = jnp.where(tok_valid[:, None, None],
                               v, 0.0).astype(buf_v.dtype)
                # buffers/planes are head-sharded: this shard sees only
                # its kv heads (and their kv-head-major query groups)
                km = self._local_heads(km, 1)
                vm = self._local_heads(vm, 1)
                q = self._local_heads(q, 1)
                buf_k = buf_k.at[lidx].set(km)
                buf_v = buf_v.at[lidx].set(vm)

                state_l = cache.slot_state[lidx]                 # [NS]
                bits_l = cache.slot_bits[lidx]
                table_l = table[lidx]                            # [NB]
                # query t sees chunk tokens j <= t (self-inclusive)
                buf_mask = (jnp.arange(C)[None, :] <=
                            jnp.arange(C)[:, None]) & tok_valid[None, :]

                is_calib = jnp.any(lidx == lstar)

                def dense():
                    o, p, valid = self._dense_layer(
                        q, kc_l, vc_l, ks_l, vs_l, state_l, bits_l,
                        table_l, km, vm, buf_mask)
                    last = jnp.clip(n_valid - 1, 0, C - 1)
                    return o, _probs_sparsity(p[last], valid[last], ax)

                if backend == "kernel":
                    o = self._chunk_kernel(q, kc_l, vc_l, ks_l, vs_l,
                                           state_l, bits_l, table_l,
                                           km, vm, tok_valid)
                    # dense probs only when this chunk's end is a tau
                    # boundary (the only place sparsity is consumed)
                    spars = jax.lax.cond(is_calib & refresh_due,
                                         lambda: dense()[1],
                                         lambda: jnp.float32(0))
                else:
                    o, spars = dense()

                h = h + A.out_proj(lp["attn"], self._gather_heads(o, 1))
                x2 = rmsnorm(lp["norm2"], h, cfg.norm_eps)
                m = self._ffn(lp, x2, tok_valid)[0]
                return (h + m, buf_k, buf_v), spars

            if self._kinds is None:
                (h, buf_k, buf_v), spars_all = jax.lax.scan(
                    body, (h, cache.buf_k, cache.buf_v),
                    (jnp.arange(cfg.num_layers), params["layers"],
                     pool.view.k_codes, pool.view.v_codes,
                     pool.view.k_scales, pool.view.v_scales))
                cache = cache.replace(buf_k=buf_k, buf_v=buf_v)
                sparsity = jnp.mean(spars_all[lstar])
            else:
                bufs, ring = (cache.buf_k, cache.buf_v), \
                    (cache.ring_k, cache.ring_v)
                spars_all = []
                for l, kind in enumerate(self._kinds):
                    lp = jax.tree.map(lambda x: x[l], params["layers"])
                    i = self._layer_index[l]
                    if kind == WINDOW:
                        h, ring = self._window_layer(h, ring, lp, i, start,
                                                     n_valid, tok_valid)
                        continue
                    (h, *bufs), sp = body(
                        (h, *bufs), (i, lp, *(p[i] for p in pool.view)),
                        kind)
                    spars_all.append(sp)
                cache = cache.replace(buf_k=bufs[0], buf_v=bufs[1],
                                      ring_k=ring[0], ring_v=ring[1])
                sparsity = jnp.mean(jnp.stack(spars_all)[lstar])

            pool, table, cache, fail, n_cow = CC.engine_advance(
                tk, dims, pool, table, cache, sparsity,
                jnp.bool_(True), n_new=n_valid, with_alloc_fail=True,
                track_cow=self._track_cow, axis_name=ax,
                policy=self.policy)

            h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
            last = jnp.clip(n_valid - 1, 0, C - 1)
            logits = softcap(E.unembed(params["embed"], h[last], cfg),
                             cfg.logit_softcap)
            return pool, table, cache, logits, fail, n_cow

        pool_s, cache_s, rep = self._spmd_specs(single_request=True)
        return self._wrap_spmd(
            chunk_step,
            in_specs=(rep, pool_s, rep, cache_s, rep, rep),
            out_specs=(pool_s, rep, cache_s, rep, rep, rep))

    def _ffn(self, lp, x2, valid):
        """A layer's MLP, or its held experts (dropless; tokens with
        ``valid`` False, idle slots or prefill padding, route nowhere):
        ``(residual, routed)``, ``routed`` the held experts' (assigned
        pairs, experts hit), or ``()`` for an MLP."""
        cfg = self.mcfg
        if cfg.moe is None:
            return mlp(lp["mlp"], x2, cfg.act, cfg.mlp_gated), ()
        with TR.scope(TR.MOE):
            m, assigned, hit = moe_held(lp["moe"], x2, cfg, valid=valid)
        return m, (assigned, hit)

    def _window_layer(self, h, ring, lp, i, start, n_valid, valid):
        """Window layer ``i`` over a prefill chunk ``h`` [C, D] (positions
        ``start + t``, the first ``n_valid`` real; ``valid`` their mask,
        None for a full chunk): its queries attend the ring, which holds
        the positions before ``start``, and the chunk itself, each
        within the window; then the chunk's last ``min(n_valid, W)`` keys
        and values go into the ring.  Returns ``(h, (ring_k, ring_v))``."""
        cfg, W = self.mcfg, self.dims.W
        ring_k, ring_v = ring
        t = jnp.arange(h.shape[0])
        q_pos = start + t
        x1 = rmsnorm(lp["norm1"], h, cfg.norm_eps)
        q, k, v = A.qkv_rope(lp["attn"], x1, cfg, q_pos, WINDOW)
        j = jnp.arange(W)
        # ring entry j holds the last position before start that is j
        # modulo W (negative: none yet)
        ring_pos = j + W * ((start - 1 - j) // W)
        o = _window_chunk_attend(
            q, jnp.concatenate([ring_k[i], k.astype(ring_k.dtype)]),
            jnp.concatenate([ring_v[i], v.astype(ring_v.dtype)]),
            q_pos, jnp.concatenate([ring_pos, q_pos]),
            jnp.concatenate([ring_pos >= 0, t < n_valid]), W)
        # only the last W real tokens land (earlier ones would collide)
        keep = (t < n_valid) & (t >= n_valid - W)
        at = jnp.where(keep, q_pos % W, W)
        ring_k = ring_k.at[i, at].set(k.astype(ring_k.dtype), mode="drop")
        ring_v = ring_v.at[i, at].set(v.astype(ring_v.dtype), mode="drop")
        h = h + A.out_proj(lp["attn"], o.astype(h.dtype))
        m = self._ffn(lp, rmsnorm(lp["norm2"], h, cfg.norm_eps), valid)[0]
        return h + m, (ring_k, ring_v)

    def _chunk_kernel(self, q, kc_l, vc_l, ks_l, vs_l, state_l, bits_l,
                      table_l, k_chunk, v_chunk, tok_valid):
        """Kernel path for one prefill chunk: every chunk query attends the
        FROZEN pool (queries fold into the kernel's q-group axis) merged
        with the causal intra-chunk flash part.

        ``tok_valid=None`` means the chunk is FULL (the large-chunk path):
        the intra-chunk partition then runs the compiled ``flash_prefill``
        kernel (the chunk length must be a 128-multiple, or
        ``prefill_attention_stats`` raises).  With a mask (the
        g-sized tail path, chunk <= 16 tokens — below the kernel's 128
        tile) it runs the reference oracle.
        """
        dims = self.dims
        c, hq, hd = q.shape
        h = k_chunk.shape[1]        # kv heads VISIBLE here (H/N on-mesh)
        gq = hq // h
        # [C, Hq, hd] -> [1, H, C*gq, hd]
        qh = q.reshape(c, h, gq, hd).transpose(1, 0, 2, 3) \
            .reshape(1, h, c * gq, hd).astype(jnp.float32)
        shp = (1, dims.NB, dims.BS)
        o_p, m_p, l_p = K.paged_decode_attention_batched(
            qh, kc_l, vc_l, ks_l, vs_l, state_l.reshape(shp),
            bits_l.reshape(shp), table_l[None], force=self._force)
        # back to per-query layout [C, Hq, ...]
        unfold = lambda a, d: a[0].reshape(h, c, gq, d).transpose(1, 0, 2, 3) \
            .reshape(c, hq, d)
        o_p = unfold(o_p, hd)
        m_p = unfold(m_p, 1)
        l_p = unfold(l_p, 1)
        o_c, m_c, l_c = K.prefill_attention_stats(
            q.astype(jnp.float32), k_chunk.astype(jnp.float32),
            v_chunk.astype(jnp.float32), causal=True, kv_valid=tok_valid,
            force=self._force)
        return KR.merge_flash_ref(o_p, m_p, l_p, o_c, m_c,
                                  l_c).astype(q.dtype)

    # ------------------------------------------------------------------
    def _make_prefill_big(self):
        """Large-chunk prefill: ``prefill_chunk`` (128-multiple) tokens of
        ONE slot in a single forward — the causal intra-chunk partition
        through the COMPILED ``flash_prefill`` kernel, the frozen-pool
        partition through the batched paged kernel — then C/g TBQ group
        commits in order (each enforcing budget/refresh).  See the module
        docstring for the two ways this relaxes the token-by-token cache
        evolution (fp intra-chunk visibility; one sparsity per chunk)."""
        cfg, tk, dims = self.mcfg, self.tk, self.dims
        lstar_arr = jnp.asarray(self.lstar)
        backend = self.backend
        C = self.prefill_chunk
        ax = self._axis

        def big_step(params, pool, table, cache, tokens_c):
            start = cache.num_tokens
            positions = start + jnp.arange(C, dtype=jnp.int32)
            # sparsity is consumed only if a tau boundary falls in-chunk
            has_refresh = jnp.any(
                (start + jnp.arange(1, C + 1)) % tk.refresh_interval == 0)
            h = E.embed(params["embed"], tokens_c, cfg)          # [C, Dm]

            def body(carry, inp, kind=None):
                """One full layer (``lidx`` its pool layer; ``kind`` set
                in a model with layer kinds)."""
                h = carry
                lidx, lp, kc_l, vc_l, ks_l, vs_l = inp
                x1 = rmsnorm(lp["norm1"], h, cfg.norm_eps)
                if kind is not None or \
                        cfg.position_embedding.value == "rope":
                    q, k, v = A.qkv_rope(lp["attn"], x1, cfg, positions,
                                         kind or FULL)
                else:
                    q, k, v = A._project_qkv(lp["attn"], x1, cfg)
                state_l = cache.slot_state[lidx]                 # [NS]
                bits_l = cache.slot_bits[lidx]
                table_l = table[lidx]                            # [NB]
                is_calib = jnp.any(lidx == lstar_arr)
                # attention runs on this shard's heads; k/v stay FULL in
                # the scan output (the group commits slice them locally)
                q_loc = self._local_heads(q, 1)
                k_loc = self._local_heads(k, 1)
                v_loc = self._local_heads(v, 1)

                def dense():
                    bm = jnp.arange(C)[None, :] <= jnp.arange(C)[:, None]
                    o, p, valid = self._dense_layer(
                        q_loc, kc_l, vc_l, ks_l, vs_l, state_l, bits_l,
                        table_l, k_loc, v_loc, bm)
                    return o, _probs_sparsity(p[C - 1], valid[C - 1], ax)

                if backend == "kernel":
                    o = self._chunk_kernel(q_loc, kc_l, vc_l, ks_l, vs_l,
                                           state_l, bits_l, table_l,
                                           k_loc, v_loc, None)
                    spars = jax.lax.cond(is_calib & has_refresh,
                                         lambda: dense()[1],
                                         lambda: jnp.float32(0))
                else:
                    o, spars = dense()

                h = h + A.out_proj(lp["attn"], self._gather_heads(o, 1))
                x2 = rmsnorm(lp["norm2"], h, cfg.norm_eps)
                m = self._ffn(lp, x2, None)[0]
                return h + m, (spars, k, v)

            if self._kinds is None:
                h, (spars_all, ks_all, vs_all) = jax.lax.scan(
                    body, h,
                    (jnp.arange(cfg.num_layers), params["layers"],
                     pool.view.k_codes, pool.view.v_codes,
                     pool.view.k_scales, pool.view.v_scales))
            else:
                ring, outs = (cache.ring_k, cache.ring_v), []
                for l, kind in enumerate(self._kinds):
                    lp = jax.tree.map(lambda x: x[l], params["layers"])
                    i = self._layer_index[l]
                    if kind == WINDOW:
                        h, ring = self._window_layer(h, ring, lp, i, start,
                                                     C, None)
                        continue
                    h, out = body(h, (i, lp, *(p[i] for p in pool.view)),
                                  kind)
                    outs.append(out)
                spars_all, ks_all, vs_all = (jnp.stack(a)
                                             for a in zip(*outs))
                cache = cache.replace(ring_k=ring[0], ring_v=ring[1])
            sparsity = jnp.mean(spars_all[lstar_arr])

            # commit the chunk as C/g TBQ groups, in order — the pool is
            # frozen during the forward, then each commit runs the same
            # quantize/alloc/budget/refresh sequence as a g-sized arrival
            ngroups = C // dims.G
            kg = jnp.swapaxes(
                ks_all.reshape(dims.L, ngroups, dims.G, dims.H,
                               cfg.head_dim), 0, 1)
            vg = jnp.swapaxes(
                vs_all.reshape(dims.L, ngroups, dims.G, dims.H,
                               cfg.head_dim), 0, 1)

            def commit(carry, inp):
                pool, table, cache = carry
                bk_g, bv_g = inp
                # the TBQ buffer is head-sharded: each shard commits its
                # own kv heads ([L, G, H/N, D] slice of the full group)
                cache = cache.replace(
                    buf_k=self._local_heads(bk_g, 2).astype(
                        cache.buf_k.dtype),
                    buf_v=self._local_heads(bv_g, 2).astype(
                        cache.buf_v.dtype),
                    buf_len=jnp.int32(0))
                pool, table, cache, fail, n_cow = CC.engine_advance(
                    tk, dims, pool, table, cache, sparsity, jnp.bool_(True),
                    n_new=dims.G, with_alloc_fail=True,
                    track_cow=self._track_cow, axis_name=ax,
                    policy=self.policy)
                return (pool, table, cache), (fail, n_cow)

            (pool, table, cache), (fails, n_cows) = jax.lax.scan(
                commit, (pool, table, cache), (kg, vg))

            h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
            logits = softcap(E.unembed(params["embed"], h[C - 1], cfg),
                             cfg.logit_softcap)
            return (pool, table, cache, logits, jnp.any(fails),
                    jnp.sum(n_cows))

        pool_s, cache_s, rep = self._spmd_specs(single_request=True)
        return self._wrap_spmd(
            big_step,
            in_specs=(rep, pool_s, rep, cache_s, rep),
            out_specs=(pool_s, rep, cache_s, rep, rep, rep))

    # ------------------------------------------------------------------
    # logit-drift probe (quality telemetry; see docs/policy.md)
    # ------------------------------------------------------------------

    def _make_drift_probe(self):
        """Uncompressed REFERENCE forward for the drift probe: a dense
        teacher-forced pass (no ThinKV cache, no quantization, no
        eviction) over one request's ``prompt + output`` tokens,
        returning the logits at EVERY position.  Built from the same
        blocks as ``serve_step.make_prefill_step`` (assemble_inputs →
        backbone → unembed), so its numerics are the established dense
        path, not a third implementation.

        The probe runs replicated (plain jit, no shard_map): it is
        per-finished-request telemetry off the tick hot path.  Causal
        attention makes right-padding harmless — positions < length are
        bit-independent of the pad tail."""
        cfg = self.mcfg

        def probe(params, tokens):
            from repro.models import lm
            h, positions = lm.assemble_inputs(params, {"tokens": tokens},
                                              cfg)
            h, _ = lm.backbone(params, h, cfg, positions, remat=True)
            lg = E.unembed(params["embed"], h, cfg)
            return softcap(lg, cfg.logit_softcap)

        return probe

    def measure_drift(self, prompt: np.ndarray, output: Sequence[int],
                      recorded: Sequence[np.ndarray]) -> Dict[str, float]:
        """Compare a finished request's RECORDED serving logits (one
        [V] array per emitted token: the prefill boundary + each decode
        tick) against the uncompressed dense replay of the same token
        sequence.  Returns per-request drift metrics.

        ``recorded[i]`` predicted ``output[i]`` from the COMPRESSED
        cache state at context ``prompt + output[:i]``; the reference
        replay's position ``len(prompt) - 1 + i`` predicts the same
        token from the full-precision context.  The delta therefore
        folds in everything the serving path does differently —
        quantization, progressive eviction, AND the attention-late tick
        dataflow.  That dataflow is identical across retention policies,
        so cross-policy drift comparisons isolate the policy."""
        assert self.drift_probe, "engine built without drift_probe=True"
        p = int(len(prompt))
        toks = np.concatenate([np.asarray(prompt, np.int64),
                               np.asarray(list(output), np.int64)])
        n = len(toks) - 1 if len(output) else len(toks)
        pad = -(-max(n, 1) // DRIFT_PAD) * DRIFT_PAD
        buf = np.zeros((1, pad), np.int32)
        buf[0, :n] = toks[:n]
        ref = self._fetch(self._drift_probe_jit(self.params,
                                                jnp.asarray(buf)))[0]
        steps = min(len(output), len(recorded))
        max_abs = mean_abs = 0.0
        top1 = 0
        for i in range(steps):
            got = np.asarray(recorded[i], np.float32).reshape(-1)
            want = ref[p - 1 + i].astype(np.float32)
            d = np.abs(got - want)
            max_abs = max(max_abs, float(d.max()))
            mean_abs += float(d.mean())
            top1 += int(np.argmax(got) == np.argmax(want))
        out = {
            "steps": steps,
            "max_abs": max_abs,
            "mean_abs": mean_abs / max(steps, 1),
            "top1_agree": top1 / max(steps, 1),
        }
        self.metrics["drift_probes"] += 1
        self.metrics["drift_max_abs"] = max(
            self.metrics["drift_max_abs"], max_abs)
        return out

    # ------------------------------------------------------------------
    # compiled-path contract auditing (repro.analysis)
    # ------------------------------------------------------------------

    def compiled_entry_points(self) -> Dict[str, tuple]:
        """``{name: (unjitted fn, representative args)}`` for every
        compiled entry point — the registry ``repro.analysis`` audits
        (``audit_engine``) and ``RetraceGuard`` wraps.  Adding a new
        jitted path to the engine REQUIRES registering it here AND
        declaring its ``CompiledContract`` in
        ``analysis.contracts.engine_contracts`` (``audit_engine`` raises
        on a registered path with no contract; see docs/analysis.md)."""
        R = self.cfg.max_seqs
        cache0 = jax.tree.map(lambda x: x[0], self.caches)
        eps = {
            "_tick_fn": (self._tick_fn, (
                self.params, self.pool, self.tables, self.caches,
                jnp.zeros(R, jnp.int32), jnp.ones(R, bool),
                self._slot_rng)),
            "_prefill_chunk_fn": (self._prefill_chunk_fn, (
                self.params, self.pool, self.tables[0], cache0,
                jnp.zeros(self.dims.G, jnp.int32),
                jnp.int32(self.dims.G))),
        }
        if self._megatick_fn is not None:
            eps["_megatick_fn"] = (self._megatick_fn, (
                self.params, self.pool, self.tables, self.caches,
                jnp.zeros(R, jnp.int32), jnp.ones(R, bool),
                self._slot_rng, jnp.full(R, 4, jnp.int32),
                jnp.full(R, -1, jnp.int32),
                jnp.int32(self.ticks_per_dispatch)))
        if self._prefill_big_fn is not None:
            eps["_prefill_big_fn"] = (self._prefill_big_fn, (
                self.params, self.pool, self.tables[0], cache0,
                jnp.zeros(self.prefill_chunk, jnp.int32)))
        if self._drift_probe_fn is not None:
            eps["_drift_probe_fn"] = (self._drift_probe_fn, (
                self.params,
                jnp.zeros((1, DRIFT_PAD), jnp.int32)))
        return eps

    def audit_compiled(self):
        """Full contract audit of every compiled entry point ->
        ``analysis.AuditReport`` (launch counts, collectives, callbacks,
        precision — see docs/analysis.md)."""
        from repro.analysis import audit_engine
        return audit_engine(self)

    def _entry_census(self, name: str):
        from repro.analysis.jaxpr_audit import census_of
        fn, args = self.compiled_entry_points()[name]
        return census_of(jax.make_jaxpr(fn)(*args))

    def tick_launch_count(self) -> int:
        """Per-tick ``pallas_call`` LAUNCH count from the decode tick's
        jaxpr census (``repro.analysis``; scan bodies multiplied by trip
        count — a kernel inside the layer scan would count L times).
        The fused kernel backend is exactly 1 at any layer count;
        reference is 0."""
        return self._entry_census("_tick_fn").launches_at(1)

    def megatick_launch_count(self) -> tuple:
        """``(per_trip, outside)`` pallas launch counts of the
        mega-dispatch from its jaxpr census — launches per fused TICK
        (the while body) and launches OUTSIDE the loop.  The
        single-launch contract extends to the mega-dispatch as
        ``per_trip == tick_launch_count()`` (exactly 1 on the kernel
        backend, 0 on reference) with ``outside == 0`` — fusing N ticks
        dispatches N kernel launches in one XLA program, never N
        programs and never stray launches around the loop."""
        assert self._megatick_fn is not None, \
            "mega-dispatch disabled (ticks_per_dispatch == 1)"
        c = self._entry_census("_megatick_fn")
        return c.launches_per_trip, c.launches

    def prefill_launch_count(self) -> int:
        """Per-g-chunk ``pallas_call`` launch count from the prefill
        chunk's jaxpr census — a request's total prefill launches are
        ``prefill_chunks * this`` (+ the big-chunk path's own count), so
        a prefix-cache hit that skips every covered chunk provably
        dispatched ZERO kernel launches for the covered prefix."""
        return self._entry_census("_prefill_chunk_fn").launches_at(1)

    def _make_reset(self):
        dims = self.dims

        def reset(caches, slot_idx):
            fresh = CC.init_cache(dims)
            return jax.tree.map(lambda all_, f: all_.at[slot_idx].set(f),
                                caches, fresh)
        return reset

    # ------------------------------------------------------------------
    # host-side loop
    # ------------------------------------------------------------------

    def submit(self, prompts: Sequence[np.ndarray], max_new_tokens: int,
               eos_token: Optional[int] = None,
               priorities: Optional[Sequence[int]] = None):
        for i, p in enumerate(prompts):
            req = Request(
                uid=i, prompt=np.asarray(p, np.int32),
                max_new_tokens=max_new_tokens, eos_token=eos_token,
                priority=0 if priorities is None else int(priorities[i]))
            self.scheduler.submit(req)
            self._queued_at[req.arrival] = self.metrics["ticks"]

    # ------------------------------------------------------------------
    # oversubscribed-pool admission + preemption (host side)
    # ------------------------------------------------------------------

    def _fetch(self, x):
        """Every blocking device-to-host read the engine's host paths
        make, outside the tick's result fetch, goes through here:
        ``jax.device_get`` of an array or a pytree of arrays, counted
        once in ``metrics["host_syncs"]`` and spanned as
        ``engine/sync``."""
        self.metrics["host_syncs"] += 1
        with TR.span(TR.SYNC):
            return jax.device_get(x)

    def _free_per_layer(self) -> np.ndarray:
        return self._fetch(jnp.sum(self.pool.free, axis=1)).astype(np.int64)

    def _split_table(self, table_np: np.ndarray, rc: np.ndarray = None):
        """``[L, NB]`` (private, shared) masks of a raw block table
        against the refcounts (``rc``: a pre-fetched host copy — pass it
        when a loop consults several tables so one device transfer
        serves the whole pass).

        A block is PRIVATE iff this table holds its only reference
        (refcount 1); releasing the table frees exactly its private
        blocks, and only its shared blocks can demand COW claims.  The
        single definition keeps preemption spilling, headroom estimates,
        and victim scoring consistent."""
        if rc is None:
            rc = self._fetch(self.pool.refcount)             # [L, NP]
        mapped = table_np >= 0
        rc_at = np.take_along_axis(rc, np.clip(table_np, 0, None), axis=1)
        private = mapped & (rc_at == 1)
        return private, mapped & ~private

    def _split_held(self, i: int, rc: np.ndarray = None):
        """Per-layer (private, shared) mapped-block counts of slot ``i``."""
        private, shared = self._split_table(self._fetch(self.tables[i]), rc)
        return (private.sum(axis=1).astype(np.int64),
                shared.sum(axis=1).astype(np.int64))

    def _blocks_held(self, i: int) -> np.ndarray:
        """Per-layer PRIVATE physical blocks of slot ``i`` ([L]) — the
        blocks preempting it would actually return to the free list."""
        return self._split_held(i)[0]

    def _commit_due(self, i: int) -> bool:
        """Does slot ``i``'s NEXT written token trigger a group commit?"""
        return (self._slot_ntok[i] + 1) % self.dims.G == 0

    def _cow_demand(self, i: int, rc: np.ndarray) -> int:
        """Worst-case extra fresh blocks slot ``i``'s next commit can
        claim through COW faults: every shared block it maps could be
        dirtied at once (each COWs at most once — the copy is private).
        ``rc`` is the caller's pre-fetched refcount copy; None means the
        caller established no block can be shared (demand provably 0)."""
        return int(self._split_held(i, rc)[1].max()) if rc is not None \
            else 0

    def _sharing_possible(self) -> bool:
        """Can ANY refcount currently exceed 1?  False while the prefix
        cache holds no entry, no hit ever mapped shared blocks into a
        slot, no spilled request retains shared references, and no
        fork ever increfed a parent's blocks — the headroom paths then
        skip the [L, NP] refcount transfer entirely (every COW demand
        is provably zero)."""
        if self.metrics["forks"] > 0:
            return True
        return self.prefix_cache is not None and (
            bool(self.prefix_cache.entries)
            or self.metrics["prefix_hits"] > 0
            or any(st.shared_table is not None
                   and (st.shared_table >= 0).any()
                   for st in self._spilled.values()))

    def _decay_prefix_cache(self, needed: "np.ndarray | int",
                            free: np.ndarray = None) -> bool:
        """Evict prefix-cache entries until every layer's free count
        reaches ``needed``, the cache is empty, or no cached block can
        possibly free.  Runs BEFORE any request preemption: dropping a
        cache reference can free blocks without pausing work.  Returns
        True if any entry was evicted.  ``free`` is an optional
        pre-fetched free count for the first pressure check (the caller
        usually just computed it).

        Decay only helps for UNREFERENCED cached blocks — ones whose
        every reference is a cache entry's (overlapping boundary entries
        included).  When no such block exists (every cached block is
        also mapped by a running/preempted request), evicting would wipe
        future hit opportunities without freeing a single block, so the
        loop stops and lets the caller preempt instead.  Among entries,
        the victim is the LRU entry that frees at least one block RIGHT
        NOW (some block at refcount 1); only when frees are chained
        behind overlapping boundary entries (cache-only blocks all at
        refcount >= 2) does plain LRU order break the chain.  The
        most-recently-used entry is never picked while any other entry
        remains — an admission-gate probe freshens the entry its
        shrunken watermark estimate relies on, so that entry must be the
        LAST thing decay takes."""
        if self.prefix_cache is None:
            return False
        if free is None:
            free = self._free_per_layer()
        if not (self.prefix_cache.entries and (free < needed).any()):
            return False
        # ONE refcount transfer per call; evictions are mirrored on the
        # host copies (only this loop mutates the pool while it runs)
        rc = self._fetch(self.pool.refcount).copy()          # [L, NP]
        cache_refs = np.zeros_like(rc)
        for t in self.prefix_cache.cached_tables():
            for l in range(self.dims.L):
                np.add.at(cache_refs[l], t[l][t[l] >= 0], 1)
        evicted = False
        while self.prefix_cache.entries and (free < needed).any():
            if not ((cache_refs > 0) & (cache_refs == rc)).any():
                break            # nothing decay could ever free
            lru = self.prefix_cache.lru_entries()
            cand = lru[:-1] if len(lru) > 1 else lru   # spare the MRU
            pick = next(
                (e for e in cand
                 if (self._split_table(e.table, rc)[0]).any()), cand[0])
            for l in range(self.dims.L):
                ids = pick.table[l][pick.table[l] >= 0]
                np.subtract.at(rc[l], ids, 1)
                np.subtract.at(cache_refs[l], ids, 1)
            self.pool = self.prefix_cache.evict_entry(self.pool, pick)
            evicted = True
            free = (rc == 0).sum(axis=1).astype(np.int64)
        return evicted

    def _demote_spilled_shared(self) -> bool:
        """LAST-RESORT pressure valve: convert every spilled request's
        retained shared references into plain private spill state —
        decref the shared blocks and fold them into ``st.mapped``, so
        resume claims fresh blocks and scatters the already-spilled
        planes instead of re-attaching.  Sound because the spill's view
        snapshots EVERY mapped block's planes and shared content is
        immutable from spill time (any other holder's write COW-faults
        away), so the resumed request stays bit-exact.

        This unpins the pool when retained references would otherwise
        deadlock it: a block co-held by a cache entry and a spill has
        refcount 2 with ``cache_refs == 1``, so decay refuses it and
        preemption retained it — each mechanism deferring to the other.
        After demotion the cache is the blocks' only holder and decay
        can free them.  Returns True if any reference was released."""
        changed = False
        for st in self._spilled.values():
            if st.shared_table is None or not (st.shared_table >= 0).any():
                continue
            self.pool = CC.release_blocks(self.dims, self.pool,
                                          jnp.asarray(st.shared_table))
            st.mapped = st.mapped | (st.shared_table >= 0)
            st.shared_table = None
            changed = True
        return changed

    def _watermark_blocks(self, req: Request) -> np.ndarray:
        """Per-layer block estimate for admitting ``req`` ([L]).

        A PREEMPTED request's demand is exact — its spilled mapping — plus
        one commit's claim of headroom.  A fresh request is estimated from
        the eviction budget: budget eviction runs at every commit, so valid
        tokens/layer never exceed ``token_budget + g``; ``ceil((budget+g) /
        BS)`` blocks plus one commit's claim covers the steady state
        (capped by NB, and by the request's own total length when shorter).
        This is deliberately NOT the dense worst case — over-optimism is
        repaired by preemption, never by data loss.

        A PREFIX-CACHE hit shrinks a fresh request's estimate by the
        cached-prefix blocks: shared blocks are mapped by incref, not
        claimed from the free list (later COW faults repair any
        optimism, like the rest of the estimate).  A preempted request's
        retained shared blocks likewise cost nothing to re-attach —
        ``st.mapped`` is already only the private spill."""
        dims = self.dims
        st = self._spilled.get(req.arrival)
        if st is not None:
            return st.mapped.sum(axis=1).astype(np.int64) + self._cc
        total = len(req.prompt) + int(req.max_new_tokens)
        cap = min(total, self.tk.token_budget + dims.G)
        est = np.full(dims.L,
                      min(dims.NB, -(-cap // dims.BS) + self._cc), np.int64)
        if self.prefix_cache is not None:
            # record=False: a gate probe, not a served hit — but the
            # lookup still freshens the entry's LRU stamp, and decay
            # spares the MRU entry, so the decay this same gate may
            # trigger evicts the entry the shrunken estimate relies on
            # LAST, not first
            hit = self.prefix_cache.lookup(req.prompt, record=False)
            if hit is not None:
                est = np.maximum(est - hit.blocks_per_layer, self._cc)
        return est

    def _admission_gate(self):
        """Watermark admission closure for ONE admit() sweep (per-request).

        Admit while every layer's free-block count stays at or above the
        request's watermark estimate, after reserving one commit's claim
        per already-running slot (the LOW WATERMARK — admission must never
        starve in-flight requests straight into preemption).  Each
        admission reserves its own estimate for the rest of the sweep, so
        a single stale free-count cannot over-admit.  When the gate would
        refuse, UNREFERENCED prefix-cache entries decay first (LRU) — a
        cache reference freed is cheaper than a refused admission."""
        running = sum(not s.free for s in self.scheduler.slots)
        # ONE device sync per sweep; re-read only after a decay actually
        # changed the pool (size-aware admission probes every queued
        # request, so a per-probe sync would cost a roundtrip per entry)
        state = {"reserved": np.full(self.dims.L, running * self._cc,
                                     np.int64),
                 "free": self._free_per_layer()}

        def gate(req: Request) -> bool:
            need = self._watermark_blocks(req)
            while True:
                if np.all(state["free"] - state["reserved"] >= need):
                    state["reserved"] = state["reserved"] + need
                    return True
                if not self._decay_prefix_cache(need + state["reserved"]):
                    return False
                state["free"] = self._free_per_layer()
        return gate

    def _victim_exclude(self) -> tuple:
        """Slots that must never be chosen as preemption victims: ones
        whose request has not started (admitted this sweep, prefill not
        yet run — they hold no blocks, so spilling them frees nothing and
        would capture an EMPTY cache that resume could never replay)."""
        return tuple(s.idx for s in self.scheduler.active_slots()
                     if self._slot_ntok[s.idx] == 0)

    def _preempt(self, slot) -> None:
        """Pause a RUNNING request: spill its PRIVATE pool blocks + block
        table + cache metadata/TBQ buffer to a host-side
        :class:`PreemptedState` and decref them to the global free list.
        SHARED blocks (refcount > 1: prefix-cached or mapped by another
        holder) are not spilled — releasing them would free no memory and
        their content is pinned immutable by the remaining holders — the
        victim RETAINS its reference and re-attaches them on resume."""
        i = slot.idx
        req = slot.request
        assert self._slot_ntok[i] > 0, \
            "preempting a slot that never started (nothing to spill)"
        table_np = self._fetch(self.tables[i])               # [L, NB]
        private, shared = self._split_table(table_np)
        view, _ = CC.extract_request(self.dims, self.pool, self.tables[i])
        view, cache, rng = self._fetch(
            (tuple(view), jax.tree.map(lambda x: x[i], self.caches),
             self._slot_rng[i]))
        self._spilled[req.arrival] = PreemptedState(
            view=view,
            mapped=private,
            cache=cache,
            tokens_out=slot.tokens_out,
            next_token=int(self._feed[i]),
            shared_table=np.where(shared, table_np, -1).astype(np.int32),
            rng=rng)
        # decref only the private blocks; the shared references ride
        # along in the spill (audited via audit_pool)
        self._release_slot(
            i, jnp.asarray(np.where(private, table_np, -1).astype(np.int32)))
        self.scheduler.preempt(slot)
        self._queued_at[req.arrival] = self.metrics["ticks"]
        self.metrics["preemptions"] += 1

    def _resume(self, slot, st: PreemptedState) -> bool:
        """Re-admit a preempted request bit-exactly via :meth:`insert`
        (claim fresh physical blocks for the spilled PRIVATE mapping,
        scatter the planes back, re-attach retained shared blocks
        verbatim) and restore the scheduler-side bookkeeping.

        Returns False (leaving pool and slot state untouched, the partial
        claim released) when the free list cannot back the full mapping —
        possible when an earlier admission in the SAME sweep overclaimed
        past its watermark estimate (thought-type block fragmentation can
        exceed the dense-packing estimate); the caller re-spills and
        re-queues, and the next sweep's gate sees true free counts."""
        prefix = Prefix(length=int(st.cache.num_tokens),
                        first_token=st.next_token,
                        logits=None, state=st)
        if not self.insert(prefix, slot.idx):
            return False
        slot.tokens_out = st.tokens_out
        self.metrics["resumes"] += 1
        return True

    def _ensure_decode_headroom(self) -> None:
        """Preempt AHEAD of need so the coming tick cannot hit an
        allocation failure: each slot whose next token triggers a group
        commit can claim at most ``ceil(g/BS)`` fresh blocks per layer
        PLUS one block per shared block it maps (a dirty shared block
        COW-faults into a fresh claim), and frees only add, so covering
        the committing slots from the free list is sufficient.  Before
        any victim is paused, unreferenced prefix-cache entries decay
        (LRU) — cache references are the cheapest thing to free.
        Victims: lowest priority, then most private blocks held.
        Preempting the last committing slot zeroes the demand, so this
        always terminates without raising."""
        sch = self.scheduler
        committing = {s.idx for s in sch.active_slots()
                      if self._commit_due(s.idx)}
        if not committing:
            return
        # ONE refcount transfer serves every per-slot demand estimate
        # (and none at all while nothing can be shared)
        rc = self._fetch(self.pool.refcount) \
            if self._sharing_possible() else None
        demand = {i: self._cc + self._cow_demand(i, rc) for i in committing}
        need = sum(demand.values())
        free = (rc == 0).sum(axis=1).astype(np.int64) if rc is not None \
            else self._free_per_layer()
        if self._decay_prefix_cache(need, free=free):
            free = self._free_per_layer()
        while need > 0 and int(free.min()) < need:
            victim = sch.select_victim(
                lambda i: int(self._blocks_held(i).max()),
                exclude=self._victim_exclude())
            assert victim is not None    # a committing slot always remains
            free = free + self._blocks_held(victim.idx)
            if victim.idx in committing:
                committing.discard(victim.idx)
                need -= demand.pop(victim.idx)
            self._preempt(victim)

    def _safe_decode_trips(self, cap: int, active_idx) -> int:
        """Largest trip count ``T <= cap`` whose worst-case commit claims
        the free list provably covers — the host-precomputed exit bound
        of the mega-dispatch, derived from the PR 3 watermark machinery.

        Over ``T`` ticks slot ``i`` commits ``(ntok_i % G + T) // G``
        times, each claiming at most ``ceil(G/BS)`` fresh blocks per
        layer, plus at most ONE COW claim per shared block it maps (a
        block COWs once — the copy is private).  Frees only add to the
        free list mid-pack, so covering the total claim from today's
        free count is sufficient.  ``T = 1`` is always safe: the caller
        just ran :meth:`_ensure_decode_headroom`, which preempted until
        one tick's commits fit."""
        if cap <= 1:
            return 1
        rc = self._fetch(self.pool.refcount) \
            if self._sharing_possible() else None
        free = (rc == 0).sum(axis=1).astype(np.int64) if rc is not None \
            else self._free_per_layer()
        budget = int(free.min())
        cow_extra = sum(self._cow_demand(i, rc) for i in active_idx)
        G = self.dims.G
        trips = 1
        for T in range(2, cap + 1):
            claims = sum((int(self._slot_ntok[i]) % G + T) // G
                         for i in active_idx) * self._cc + cow_extra
            if claims > budget:
                break
            trips = T
        return trips

    def _ensure_prefill_headroom(self, idx: int, n_blocks: int) -> None:
        """Free headroom for one prefill-chunk commit of slot ``idx``
        (including its potential COW claims), decaying prefix-cache
        entries first, then preempting OTHER running slots.  Raises only
        when nothing is preemptible and the pool still cannot back the
        commit (a pool too small for a single request)."""
        rc = self._fetch(self.pool.refcount) \
            if self._sharing_possible() else None
        n_blocks = n_blocks + self._cow_demand(idx, rc)
        free = (rc == 0).sum(axis=1).astype(np.int64) if rc is not None \
            else self._free_per_layer()
        if self._decay_prefix_cache(n_blocks, free=free):
            free = self._free_per_layer()
        while int(free.min()) < n_blocks:
            victim = self.scheduler.select_victim(
                lambda i: int(self._blocks_held(i).max()),
                exclude=(idx,) + self._victim_exclude())
            if victim is None:
                # last resort before declaring the pool too small:
                # unpin spilled requests' retained shared references so
                # cache decay can actually free the co-held blocks
                if self._demote_spilled_shared():
                    self._decay_prefix_cache(n_blocks)
                    free = self._free_per_layer()
                    if int(free.min()) >= n_blocks:
                        break
                raise RuntimeError(
                    f"pool exhausted: {self.num_pool_blocks} physical "
                    f"blocks cannot back one prefill commit "
                    f"({n_blocks} blocks/layer) for the only "
                    f"block-holding request — nothing is preemptible")
            free = free + self._blocks_held(victim.idx)
            self._preempt(victim)

    def _release_slot(self, i: int, table=None):
        """Decref ``table`` (default: everything slot ``i`` maps — the
        retire path; ``_preempt`` passes only the victim's PRIVATE
        mapping) and reset the slot's device + host state."""
        self.pool = CC.release_blocks(
            self.dims, self.pool,
            self.tables[i] if table is None else table)
        self.tables = self.tables.at[i].set(CC.init_block_table(self.dims))
        self.caches = self._reset_slot(self.caches, jnp.int32(i))
        self._slot_ntok[i] = 0
        self._forked[i] = False

    def audit_pool(self) -> Dict:
        """Assert the refcount accounting invariants across EVERY
        reference holder: live slot tables, prefix-cache entries, and
        preempted requests' retained shared mappings.  Raises
        AssertionError on any violation (leak, phantom ref, double-free,
        claimed+free != pool_blocks); returns per-layer counts."""
        extra = [st.shared_table for st in self._spilled.values()
                 if st.shared_table is not None]
        if self.prefix_cache is not None:
            extra += self.prefix_cache.cached_tables()
        return CC.check_pool_invariants(self.pool, self.tables, extra)

    def _prefill(self, i: int, prompt: np.ndarray) -> np.ndarray:
        """Chunked batched prefill of one slot; returns last-token logits.

        Prompts are consumed as large 128-multiple chunks first (compiled
        ``flash_prefill`` for the intra-chunk causal part, multiple group
        commits per chunk), then the tail in chunks of g.  Large chunks
        require an empty TBQ buffer, which holds here: prefill starts from
        a fresh slot and every chunk size is a multiple of g.

        Pool pressure: each g-sized chunk commits at most once (claiming
        <= ceil(g/BS) fresh blocks/layer), checked — and covered by
        preempting other slots — before every call.  A LARGE chunk commits
        C/g groups inside ONE jitted call, so the host only observes frees
        between calls; when the free list cannot cover the chunk's
        worst-case claim the prompt falls back to g-sized chunks instead
        (same math, per-commit granularity).

        PREFIX CACHE: when enabled, the longest cached prefix of the
        prompt is mapped straight into the block table (refcount++) with
        its metadata snapshot, and the covered chunks are SKIPPED — an
        exact full-prompt hit returns the cached boundary logits with
        zero forward passes.  Commit-aligned boundaries of the computed
        chunks are registered back into the cache."""
        dims = self.dims
        C = dims.G
        BC = self.prefill_chunk
        cache_i = jax.tree.map(lambda x: x[i], self.caches)
        table_i = self.tables[i]
        logits = None
        fails = []
        s0 = 0
        pc = self.prefix_cache
        hit = pc.lookup(prompt) if pc is not None else None
        if hit is not None:
            # map the shared blocks (one new reference) and restore the
            # boundary snapshot; prefill continues at the covered length
            self.pool = CC.incref_blocks(self.dims, self.pool,
                                         jnp.asarray(hit.table))
            table_i = jnp.asarray(hit.table)
            cache_i = jax.tree.map(jnp.asarray, hit.cache)
            logits = hit.logits
            s0 = hit.length
            self.metrics["prefix_hits"] += 1
            self.metrics["prefix_tokens_skipped"] += s0

        def register(boundary, logits_b):
            """Index the committed state at ``boundary`` tokens (partial
            TBQ buffer => exact-match-only entry)."""
            if pc is None or logits_b is None or boundary <= 0:
                return
            self.pool = pc.register(
                self.pool, prompt, boundary, table_i, cache_i, logits_b,
                full_only=boundary % C != 0)

        big_claims = (BC // C) * self._cc if BC else 0
        while BC and len(prompt) - s0 >= BC:
            # worst-case free blocks one big chunk can need per layer: its
            # C/g commits claim <= ceil(g/BS) each with no frees in
            # between, but the logical table caps net growth at NB -
            # mapped — any claim beyond that is preceded by at least as
            # many in-chunk frees, which replenish the free list first.
            # Shared blocks add one potential COW claim each (the copy is
            # NEW pool demand: the source stays claimed by other holders)
            self.tables = self.tables.at[i].set(table_i)
            t_np = self._fetch(table_i)
            rc = self._fetch(self.pool.refcount)  # ONE transfer per chunk
            shared = self._split_table(t_np, rc)[1]
            mapped = (t_np >= 0).sum(axis=1)                  # [L]
            need = np.minimum(big_claims, dims.NB - mapped) + \
                shared.sum(axis=1)
            free = (rc == 0).sum(axis=1).astype(np.int64)
            if self._decay_prefix_cache(need, free=free):
                free = self._free_per_layer()
            if (free < need).any():
                break            # tight pool: g-sized chunks from here on
            chunk = np.asarray(prompt[s0:s0 + BC], np.int32)
            (self.pool, table_i, cache_i, logits, fail,
             n_cow) = self._prefill_big(
                self.params, self.pool, table_i, cache_i,
                jnp.asarray(chunk))
            fails.append(fail)
            self.metrics["prefill_big_chunks"] += 1
            self.metrics["cow_faults"] += int(self._fetch(n_cow))
            s0 += BC
            register(s0, logits)
        for s in range(s0, len(prompt), C):
            # NOTE the slot's own partial state is committed to self.pool /
            # self.tables only at the end of _prefill, but headroom-driven
            # preemption of OTHER slots mutates them mid-loop — re-read the
            # pool before each chunk call, never cache it across chunks
            self.tables = self.tables.at[i].set(table_i)
            self._ensure_prefill_headroom(i, self._cc)
            chunk = prompt[s:s + C]
            n_valid = len(chunk)
            padded = np.zeros(C, np.int32)
            padded[:n_valid] = chunk
            (self.pool, table_i, cache_i, logits, fail,
             n_cow) = self._prefill_chunk(
                self.params, self.pool, table_i, cache_i,
                jnp.asarray(padded), jnp.int32(n_valid))
            fails.append(fail)
            self.metrics["prefill_chunks"] += 1
            self.metrics["cow_faults"] += int(self._fetch(n_cow))
            register(s + n_valid, logits)
        self.metrics["prefill_tokens"] += len(prompt) - (hit.length
                                                         if hit else 0)
        self._slot_ntok[i] = len(prompt)
        self.tables = self.tables.at[i].set(table_i)
        self.caches = jax.tree.map(
            lambda all_, one: all_.at[i].set(one), self.caches, cache_i)
        if any(bool(f) for f in self._fetch(fails)):
            raise AssertionError(
                "prefill commit allocation failed despite headroom checks "
                "(pool accounting bug — data would have been dropped)")
        logits = self._fetch(logits)
        if self.record_logits:
            self.trace.append({"kind": "prefill", "slot": i,
                               "logits": logits})
        return logits

    # ------------------------------------------------------------------
    # the device-facing API seam: prefill / insert / generate /
    # free_resource (JetStream-shaped; the asyncio orchestrator in
    # ``serving.orchestrator`` is the only host loop built on it)
    # ------------------------------------------------------------------

    def prefill(self, prompt: np.ndarray, slot_idx: int, rng=None,
                arrival: Optional[int] = None):
        """Chunked prefill of ``prompt`` into ``slot_idx`` + first-token
        sampling; returns ``(Prefix, rng)``.

        The returned :class:`Prefix` is RESIDENT: the committed KV lives
        in the pool under the slot's block table (prefix-cache hits and
        headroom preemption of other slots all happened inside).

        Sampling goes through the request's PRIVATE stream
        (:func:`repro.serving.sampling.request_stream_key`): ``arrival``
        seeds the stream, the boundary token is its first draw, and
        decode ticks keep advancing it — so a request's temperature>0
        tokens depend only on its identity and its logits sequence,
        never on batch composition or dispatch granularity.  Greedy
        consumes no randomness (and matches ``np.argmax`` bit-exactly).
        The legacy ``rng`` argument is threaded through untouched for
        caller-loop compatibility; ``arrival=None`` falls back to the
        slot index (single-shot harnesses without a scheduler)."""
        with TR.span(TR.PREFILL, arrival=arrival):
            logits = self._prefill(slot_idx, np.asarray(prompt))
            key = SMP.request_stream_key(
                self.cfg.seed, slot_idx if arrival is None else arrival)
            tok, key = SMP.stream_sample(key, jnp.asarray(logits),
                                         self.cfg.temperature,
                                         self.cfg.top_p)
            self._slot_rng = self._slot_rng.at[slot_idx].set(key)
            return Prefix(length=len(prompt),
                          first_token=int(self._fetch(tok)),
                          logits=logits, slot=slot_idx), rng

    def detach_prefix(self, prefix: Prefix) -> Prefix:
        """Convert a RESIDENT prefix into the PORTABLE transfer form:
        spill the slot's planes/metadata to host numpy (the
        :class:`PreemptedState` format preemption uses) and release every
        pool reference the slot held.  Shared references are DEMOTED into
        the private mapping first (decref + respill — the spill snapshots
        every mapped block, so the round trip stays bit-exact), leaving
        the detached prefix self-contained: it pins nothing in this
        engine's pool and ``insert`` rebuilds it from fresh blocks."""
        assert prefix.state is None and prefix.slot >= 0, \
            "detach_prefix needs a RESIDENT prefix"
        i = prefix.slot
        table_np = self._fetch(self.tables[i])
        view, _ = CC.extract_request(self.dims, self.pool, self.tables[i])
        view, cache, rng = self._fetch(
            (tuple(view), jax.tree.map(lambda x: x[i], self.caches),
             self._slot_rng[i]))
        prefix.state = PreemptedState(
            view=view,
            mapped=table_np >= 0,
            cache=cache,
            tokens_out=0,
            next_token=prefix.first_token,
            rng=rng)
        self._release_slot(i)
        prefix.slot = -1
        return prefix

    def insert(self, prefix: Prefix, slot_idx: int) -> bool:
        """Materialize a :class:`Prefix` into slot ``slot_idx``.

        RESIDENT prefixes (prefill ran in this very slot) only seed the
        next-token feed.  PORTABLE prefixes — detached prefills and
        preemption spills alike — claim fresh physical blocks for the
        spilled mapping, scatter the planes back through the new table,
        re-attach any retained shared references verbatim, and restore
        the cache pytree + host bookkeeping; all reads go through the
        block table in logical order, so the inserted request's logits
        are bit-identical to one that never moved.  Returns False (pool
        untouched, partial claim released) when the free list cannot
        back the mapping."""
        i = slot_idx
        if prefix.state is None:
            assert prefix.slot == i, \
                (f"resident prefix lives in slot {prefix.slot}; detach it "
                 f"before inserting into slot {i}")
            self._feed[i] = prefix.first_token
            return True
        st = prefix.state
        pool, table_i, ok = CC.restore_request(
            self.dims, self.pool, jnp.asarray(st.mapped),
            CC.PoolView(*(jnp.asarray(p) for p in st.view)))
        if not bool(self._fetch(ok)):
            self.pool = CC.release_blocks(self.dims, pool, table_i)
            return False
        self.pool = pool
        if st.shared_table is not None:
            shared_t = jnp.asarray(st.shared_table)
            table_i = jnp.where(shared_t >= 0, shared_t, table_i)
        self.tables = self.tables.at[i].set(table_i)
        cache_i = jax.tree.map(jnp.asarray, st.cache)
        self.caches = jax.tree.map(
            lambda all_, one: all_.at[i].set(one), self.caches, cache_i)
        self._slot_ntok[i] = int(st.cache.num_tokens)
        self._feed[i] = st.next_token
        if st.rng is not None:
            self._slot_rng = self._slot_rng.at[i].set(jnp.asarray(st.rng))
        # the spilled planes came back as host numpy: re-partition the
        # restored state onto the mesh (head-sharded planes/buffers)
        self._place_state()
        return True

    def generate(self, rng):
        """Dispatch one decode pack; returns ``(result, rng)``.

        Runs the preemption headroom check first (so the in-flight commit
        cannot hit an allocation failure), then launches over every
        occupied slot and returns WITHOUT blocking: the result has
        already started its D2H copies, and the host is free to dispatch
        the next pack or a prefill while they land.  Returns ``(None,
        rng)`` — rng untouched — when headroom preempted every slot
        (nothing to tick).  The caller must route the result through
        :meth:`consume` to fold the deferred device flags (and, for a
        packed result, the executed trip count) into the metrics.

        With ``ticks_per_dispatch == 1`` this is ONE fused tick
        (:class:`ResultTokens`, sampling on-device, bit-identical greedy
        output to the historical path).  With ``ticks_per_dispatch > 1``
        it is the MEGA-DISPATCH: up to :meth:`_safe_decode_trips` ticks
        fused in one ``lax.while_loop`` launch, sampled tokens feeding
        the next trip's embedding without visiting the host, exiting
        early only at scheduling events (:class:`MultiResultTokens`).
        Host token bookkeeping is updated eagerly on the single-tick
        path and deferred to :meth:`consume` on the packed path (the
        host cannot know the executed trip count at dispatch time)."""
        with TR.span(TR.HEADROOM):
            self._ensure_decode_headroom()
            active = np.array([not s.free for s in self.scheduler.slots])
            if active.any() and self.ticks_per_dispatch > 1:
                trips = self._safe_decode_trips(
                    self.ticks_per_dispatch,
                    [s.idx for s in self.scheduler.active_slots()])
        if not active.any():
            return None, rng
        with TR.span(TR.LAUNCH):
            # split once per dispatch, exactly like the historical loop —
            # slot streams own the sampling randomness now, but callers'
            # rng sequences (and the differential trace suite's decision
            # order) stay unperturbed
            rng, _ = jax.random.split(rng)
            self.metrics["dispatches"] += 1
            if self.ticks_per_dispatch == 1:
                (nxt, self.pool, self.tables, self.caches, _, logits,
                 alloc_fail, cow_faults, self._slot_rng, counters) = \
                    self._tick(self.params, self.pool, self.tables,
                               self.caches, jnp.asarray(self._feed),
                               jnp.asarray(active), self._slot_rng)
                self.metrics["ticks"] += 1
                self.metrics["tokens"] += int(active.sum())
                self._slot_ntok[active] += 1
                return ResultTokens(tick=int(self.metrics["ticks"]),
                                    tokens=nxt, valid=active,
                                    lengths=self._slot_ntok.copy(),
                                    logits=logits, alloc_fail=alloc_fail,
                                    cow_faults=cow_faults,
                                    counters=counters), rng
            if trips < self.ticks_per_dispatch:
                self.metrics["early_exit_headroom"] += 1
            R = self.cfg.max_seqs
            remaining = np.zeros(R, np.int32)
            eos = np.full(R, -1, np.int32)
            for s in self.scheduler.active_slots():
                remaining[s.idx] = max(
                    1, int(s.request.max_new_tokens) - int(s.tokens_out))
                if s.request.eos_token is not None:
                    eos[s.idx] = int(s.request.eos_token)
            (toks, valid, logits_buf, self.pool, self.tables, self.caches,
             self._slot_rng, t, fail, cow, counters) = self._megatick(
                self.params, self.pool, self.tables, self.caches,
                jnp.asarray(self._feed), jnp.asarray(active),
                self._slot_rng, jnp.asarray(remaining), jnp.asarray(eos),
                jnp.int32(trips))
            return MultiResultTokens(base_tick=int(self.metrics["ticks"]),
                                     requested=trips, tokens=toks,
                                     valid=valid, logits=logits_buf,
                                     alloc_fail=fail, cow_faults=cow,
                                     trips=t, counters=counters), rng

    def consume(self, res) -> "ResultTokens | MultiResultTokens":
        """Fold a completed dispatch's deferred device flags into the
        host metrics (blocking on its D2H copies if they have not
        landed).  The allocation-failure assert lives here — after the
        overlapped transfer — instead of on the dispatch path.

        A PACKED result additionally settles the bookkeeping the
        dispatch deferred: the executed trip count lands in
        ``metrics["ticks"]``, per-slot valid-token counts advance the
        host token mirror (``_slot_ntok``), and each trip's logits
        become one decode trace entry — indistinguishable from ``trips``
        single-tick results.  Safe to defer because the orchestrator
        consumes a pack before the next ``generate``/``prefill`` reads
        any of that state.  COW faults on FORKED slots are attributed
        to ``metrics["fork_cow_faults"]`` (best-of-n divergence cost).
        ``metrics["attn_pages"]`` adds the mapped pages the dispatch's
        attention read, ``metrics["attn_page_slots"]`` the table entries
        it spanned (slots x pool layers x NB per tick).  With window
        layers ``metrics["window_positions"]`` adds the ring positions
        their attention read (over window layers and active slots); with
        experts ``metrics["expert_assignments"]`` adds the routed
        (token, expert) pairs of active slots that landed on held
        experts, ``metrics["experts_hit"]`` the held experts with at
        least one such pair (per layer and tick), and
        ``metrics["expert_slots"]`` layers x held experts per tick."""
        with TR.span(TR.CONSUME):
            if res.alloc_fail_host:
                raise AssertionError(
                    "decode commit allocation failed despite preemption "
                    "headroom (pool accounting bug — data would have been "
                    "dropped)")
            cow = res.cow_per_slot_host
            self.metrics["cow_faults"] += int(cow.sum())
            self.metrics["fork_cow_faults"] += int(cow[self._forked].sum())
            trips = res.trips_host if res.packed else 1
            for k, v in res.counters_host.items():
                self.metrics[k] += v
            self.metrics["attn_page_slots"] += trips * int(
                np.prod(self.tables.shape))
            if self.mcfg.moe is not None:
                self.metrics["expert_slots"] += trips * \
                    self.mcfg.num_layers * self.mcfg.moe.num_held
            if res.packed:
                if trips < res.requested:
                    self.metrics["early_exit_finish"] += 1
                counts = res.valid_host[:trips].sum(axis=0).astype(
                    np.int64)
                self.metrics["ticks"] += trips
                self.metrics["tokens"] += int(counts.sum())
                self._slot_ntok += counts
                if self.record_logits:
                    for t in range(trips):
                        self.trace.append(
                            {"kind": "decode",
                             "active": res.valid_host[t].copy(),
                             "logits": res.logits_host[t]})
            elif self.record_logits:
                self.trace.append({"kind": "decode",
                                   "active": res.valid.copy(),
                                   "logits": res.logits_host})
        return res

    def fork_slot(self, src: int, dst: int, arrival: int) -> None:
        """Fork slot ``src``'s sequence into free slot ``dst`` by
        REFERENCE: every pool block the parent maps gains one refcount
        (``incref_blocks`` — zero plane copies), the block table and
        per-slot cache pytree rows are duplicated, and the child
        inherits the parent's feed token and generated-length mirror —
        so the child continues from the parent's prompt + CoT-so-far.
        The shared blocks are immutable from here: the first commit
        either side lands on one COW-faults a private copy (tracked in
        ``metrics["fork_cow_faults"]``), which is how ``samples_per_slot``
        best-of-n divergence is paid for — one block at a time, never a
        full-cache copy.

        ``arrival`` (the child request's unique stamp) seeds the child's
        PRIVATE sampling stream, so at temperature>0 the child diverges
        from the parent on its first sampled token; at temperature 0
        both stay greedy and emit identical tokens — the fork-parity
        property the CI gate pins."""
        assert self._track_cow, \
            "fork_slot requires allow_forks=True (COW write tracking)"
        assert self._slot_ntok[src] > 0, "fork source never started"
        assert self._slot_ntok[dst] == 0, f"fork target slot {dst} in use"
        self.pool = CC.incref_blocks(self.dims, self.pool,
                                     self.tables[src])
        self.tables = self.tables.at[dst].set(self.tables[src])
        self.caches = jax.tree.map(lambda a: a.at[dst].set(a[src]),
                                   self.caches)
        self._slot_ntok[dst] = self._slot_ntok[src]
        self._feed[dst] = self._feed[src]
        self._slot_rng = self._slot_rng.at[dst].set(
            SMP.request_stream_key(self.cfg.seed, arrival))
        self._forked[src] = True
        self._forked[dst] = True
        self.metrics["forks"] += 1
        self.metrics["peak_refcount"] = max(
            self.metrics["peak_refcount"],
            int(self._fetch(self.pool.refcount).max()))

    def free_resource(self, slot_idx: int) -> None:
        """Release EVERY pool reference slot ``slot_idx`` holds — private
        blocks decref to the free list, shared blocks decref toward their
        other holders — and reset its device cache + host bookkeeping.
        Retirement and mid-flight cancellation both land here; the slot
        is immediately reusable by the next admission."""
        self._release_slot(slot_idx)

    def drop_spill(self, arrival: int) -> bool:
        """Drop a cancelled request's :class:`PreemptedState` spill,
        releasing the shared-block references it RETAINED at preemption
        time (the spilled private planes are host numpy — dropping them
        frees no pool blocks, but the retained refs would otherwise
        leak: ``audit_pool`` counts spills as reference holders)."""
        st = self._spilled.pop(arrival, None)
        if st is None:
            return False
        if st.shared_table is not None and (st.shared_table >= 0).any():
            self.pool = CC.release_blocks(
                self.dims, self.pool, jnp.asarray(st.shared_table))
        return True

    def run(self, max_ticks: int = 10_000) -> List[Request]:
        """Synchronous compatibility wrapper over the asyncio
        orchestrator: serve everything already submitted, return the
        finished requests.

        The orchestrator replays the exact decision order of the
        historical monolithic loop (admission sweeps, headroom checks,
        rng splits), so tokens, per-request logits, pool audits, and
        metrics are bit-identical to it — the differential serving-trace
        suite pins that equivalence.  Re-entry works the same way:
        ``run(max_ticks=k)`` may stop mid-flight and a later ``run()``
        picks up the surviving slot/queue state.  Raises RuntimeError
        only on a true admission livelock (see
        ``Orchestrator._admit_and_prefill``)."""
        from repro.serving.orchestrator import Orchestrator
        orch = Orchestrator(self)
        self.last_orchestrator = orch
        return orch.run_sync(max_ticks=max_ticks)

    # ------------------------------------------------------------------
    def slot_stats(self, i: int) -> Dict:
        """Footprint and cache-evolution counts of slot ``i``'s request:
        ``committed_tokens`` went through group commits, ``valid_tokens``
        [L] of them are still attended (the rest were evicted), and
        ``refreshes`` counts closed segments (only a thought refresh
        advances ``cur_seg``; it saturates at ``max_segments - 1``)."""
        one = jax.tree.map(lambda x: x[i], self.caches)
        from repro.core.thinkv import compression_ratio
        comp = compression_ratio(self.tk, self.dims, one, one.num_tokens)
        comp, ntok, buf_len, seg = self._fetch(
            (comp, one.num_tokens, one.buf_len, one.cur_seg))
        out = {k: np.asarray(v).tolist() for k, v in comp.items()}
        out["committed_tokens"] = int(ntok - buf_len)
        out["refreshes"] = int(seg)
        return out
