"""CT paged decode-attention Pallas TPU kernels (paper Sec. 5 'Continuous
Thinking', adapted per DESIGN.md Sec. 3).

The FUSED entry point (``ct_paged_attention_fused``) serves a whole
continuous-batching decode tick in ONE launch: the grid is
``(L, R, H, NB + 1)`` — a leading layer axis over the pool planes (which
already carry ``[L, NP, H, BS, ...]``), then request slots, kv heads, and
the per-sequence block walk.  The first ``NB`` steps of the last grid axis
stream quantized pool blocks through the block-table indirection; the final
step attends the full-precision TBQ buffer ``B_buf`` for the same
``(l, r, h)``, so the ``(m, l)`` flash-merge between the quantized pool and
the buffer happens in VMEM scratch — the kernel returns FINAL outputs, no
stats plumbing back to XLA.  This amortizes launch overhead over ``L`` and
removes the per-layer XLA merge einsum, the two linear-in-``L`` costs of
the per-layer launch scheme.

Shared kernel mechanics:

* the quantized cache (nibble codes + E4M3 group scales) is the ONLY HBM
  traffic for committed tokens — dequantization (code decode + scale
  multiply) is fused in VMEM before the MXU dot, which is the entire
  memory-roofline win of TBQ;
* the paper's eviction/segment masks enter as the per-slot ``slot_state``
  plane: soft-evicted slots are masked out of the softmax, never compacted;
* PagedAttention's block-table indirection is kept via scalar prefetch
  (``block_table[r, l, b] -> physical block``): the CODE/SCALE planes are
  the engine's SHARED physical pool indexed through the table, while
  ``slot_state``/``slot_bits`` are per-request logical metadata indexed
  directly — requests only ever touch physical blocks their table maps;
* every entry point accepts RAW block tables: unmapped entries are ``-1``
  sentinels and are clamped internally (their slots are FREE in the
  metadata, so the state mask zeroes their contribution) — callers never
  pre-clamp;
* flash accumulation state (m, l, acc) lives in VMEM scratch across the
  sequential block grid dimension.

The per-layer batched entry point (``ct_paged_attention_batched``) remains
for the chunked-prefill frozen-pool partition (its ``(m, l)`` stats merge
against the intra-chunk flash partition) and for tests; the single-request
wrapper remains for the single-sequence controller.  The query-group axis
``GQ`` is ``Hq // H`` for decode and ``chunk * Hq // H`` for chunked
prefill (every chunk token attends the same frozen pool, so chunk queries
fold into the q-group axis).

PER-SHARD LAUNCHES (tensor-parallel serving): no grid step ever reads
across the ``H`` axis — each ``(l, r, h, b)`` cell touches exactly one
head's tile of every operand — so the serving engine's ``shard_map``
simply calls these entry points with the head axes of queries, planes,
and buffers sliced to the shard's ``H / num_shards`` local heads (see
``kernels.ops.local_heads``).  The per-shard launch computes the exact
corresponding slice of the full launch, the grid shrinks to
``(L, R, H/n, NB + 1)``, and the fused tick stays ONE launch per shard.
The head count is a plain grid extent with no tiling constraint, so any
``H % num_shards == 0`` split compiles unchanged.

Tiling: planes are ``[..., H, BS, D]``, so one block of one head is a
(block_size=16, head_dim=128) page whose block shape equals the array's
last two dims; codes are uint8, scales a bf16 (16, D/g) page.  Per-slot
metadata enters as ``[..., 1, BS]`` (state) and ``[..., BS, 1]`` (bits)
blocks, one per page, cast to int32 in-kernel — Mosaic accepts neither a
dynamic sublane offset into a uint8 tile nor 1-D uint8 vectors.  The
kernels compile for a v5e at block_size 16 (``tests/test_tpu_compile.py``).

Validated on CPU against ``ref.ct_paged_attention_fused_ref`` /
``ref.ct_paged_attention_ref`` in interpret mode (``tests/test_kernels.py``
sweeps layer counts, shapes, dtypes, and bit-widths).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
VALID = 1


def _decode_codes(codes_u8, bits, scales, group: int):
    """Fused in-VMEM dequant of one page: [BS, D] uint8 codes -> f32.

    ``bits`` [BS, 1] int32 is the per-slot width in {2, 4, 8};
    ``scales`` [BS, D//group] holds E4M3-valued group scales.  The scales
    widen to [BS, D] through a 0/1 [D//group, D] matmul: Mosaic cannot
    reshape the lane axis into (D//group, group), and a bf16 x {0, 1}
    product summed once is exact."""
    c = codes_u8.astype(jnp.int32)
    # ternary (2b): low 2 bits; {0:+0, 1:+1, 3:-1}
    c2 = c & 3
    v2 = jnp.where(c2 == 3, -1.0, jnp.where(c2 == 1, 1.0, 0.0))
    # nvfp4 (4b): s eem arithmetic decode (no gather)
    c4 = c & 0xF
    sign = 1.0 - 2.0 * ((c4 >> 3) & 1).astype(jnp.float32)
    idx = c4 & 7
    exp = (idx >> 1).astype(jnp.float32)
    man = (idx & 1).astype(jnp.float32)
    v4 = sign * jnp.where(idx < 2, 0.5 * man,
                          (1.0 + 0.5 * man) * jnp.exp2(exp - 1.0))
    # int8 (8b): two's complement
    v8 = jnp.where(c >= 128, c - 256, c).astype(jnp.float32)
    vals = jnp.where(bits == 2, v2, jnp.where(bits == 4, v4, v8))
    ng, d = scales.shape[-1], vals.shape[-1]
    expand = (jax.lax.broadcasted_iota(jnp.int32, (ng, d), 1) // group ==
              jax.lax.broadcasted_iota(jnp.int32, (ng, d), 0))
    full = jax.lax.dot_general(
        scales.astype(jnp.bfloat16), expand.astype(jnp.bfloat16),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    return vals * full


def _page_attention(q, kc_ref, vc_ref, ks_ref, vs_ref, state_ref, bits_ref,
                    group: int, accumulate):
    """Attend one pool page: dequantize its K/V, score, and hand the
    partition to ``accumulate``.  Every ref holds exactly one page of one
    head (leading block dims are 1)."""
    lead = (0,) * (kc_ref.ndim - 2)
    bits = bits_ref[lead].astype(jnp.int32)                # [BS, 1]
    state = state_ref[lead].astype(jnp.int32)              # [1, BS]
    k = _decode_codes(kc_ref[lead], bits, ks_ref[lead], group)   # [BS, D]
    v = _decode_codes(vc_ref[lead], bits, vs_ref[lead], group)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    accumulate(s * (1.0 / (q.shape[-1] ** 0.5)), state == VALID, v)


def _flash_update(m_ref, l_ref, acc_ref, s, valid, v):
    """Online-softmax update of the (m, l, acc) scratch with one
    partition: scores ``s`` [GQ, N], ``valid`` broadcastable to it,
    values ``v`` [N, D]."""
    s = jnp.where(valid, s, NEG_INF)
    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(valid, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _init_scratch(m_ref, l_ref, acc_ref):
    acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)
    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, m_ref.dtype)
    l_ref[...] = jnp.zeros(l_ref.shape, l_ref.dtype)


def _kernel(block_table, q_ref, kc_ref, vc_ref, ks_ref, vs_ref, state_ref,
            bits_ref, o_ref, mo_ref, lo_ref, m_ref, l_ref, acc_ref, *,
            group: int, blocks_per_seq: int):
    b = pl.program_id(2)

    @pl.when(b == 0)
    def _init():
        _init_scratch(m_ref, l_ref, acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                    # [GQ, D]
    _page_attention(q, kc_ref, vc_ref, ks_ref, vs_ref, state_ref, bits_ref,
                    group, functools.partial(_flash_update, m_ref, l_ref,
                                             acc_ref))

    @pl.when(b == blocks_per_seq - 1)
    def _final():
        o_ref[0, 0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        mo_ref[0, 0] = m_ref[...]
        lo_ref[0, 0] = l_ref[...]


def _fused_kernel(bt_ref, blen_ref, q_ref, kc_ref, vc_ref, ks_ref, vs_ref,
                  state_ref, bits_ref, bk_ref, bv_ref, o_ref, m_ref, l_ref,
                  acc_ref, *, group: int, blocks_per_seq: int):
    """One (layer, request, head) flash pass: NB quantized pool blocks, then
    the fp TBQ buffer as the final grid step, final output from scratch."""
    rr = pl.program_id(1)
    b = pl.program_id(3)
    accumulate = functools.partial(_flash_update, m_ref, l_ref, acc_ref)

    @pl.when(b == 0)
    def _init():
        _init_scratch(m_ref, l_ref, acc_ref)

    q = q_ref[0, 0, 0].astype(jnp.float32)                 # [GQ, D]

    @pl.when(b < blocks_per_seq)
    def _pool_block():
        _page_attention(q, kc_ref, vc_ref, ks_ref, vs_ref, state_ref,
                        bits_ref, group, accumulate)

    @pl.when(b == blocks_per_seq)
    def _buffer_and_final():
        bk = bk_ref[0, 0, 0].astype(jnp.float32)           # [G, D]
        bv = bv_ref[0, 0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, bk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        pos = jax.lax.broadcasted_iota(jnp.int32, (1, bk.shape[0]), 1)
        accumulate(s * (1.0 / (q.shape[-1] ** 0.5)), pos < blen_ref[rr], bv)
        o_ref[0, 0, 0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def _metadata_blocks(slot_state, slot_bits):
    """[..., NB, BS] uint8 metadata -> state [..., NB, 1, BS] and bits
    [..., NB, BS, 1] (free reshapes).  Each page then reads its own
    block, whose last two dims equal the array's: the tiling check
    passes at BS=16, no dynamic sublane offset into a uint8 tile is
    needed, and no 1-D vector reaches Mosaic."""
    return slot_state[..., None, :], slot_bits[..., None]


@functools.partial(jax.jit, static_argnames=("group", "interpret"))
def ct_paged_attention_fused(qh: jax.Array, k_codes: jax.Array,
                             v_codes: jax.Array, k_scales: jax.Array,
                             v_scales: jax.Array, slot_state: jax.Array,
                             slot_bits: jax.Array, block_table: jax.Array,
                             buf_k: jax.Array, buf_v: jax.Array,
                             buf_len: jax.Array, *, group: int = 16,
                             interpret: bool = False) -> jax.Array:
    """A whole decode tick's attention in ONE launch: every layer, every
    request slot, quantized pool ∪ fp TBQ buffer, flash-merged in VMEM.

    Args:
      qh:         [L, R, H, GQ, D]   queries per layer/slot/kv-head.
      k_codes:    [L, NP, H, BS, D]  uint8 shared physical pool planes.
      v_codes:    [L, NP, H, BS, D]
      k_scales:   [L, NP, H, BS, D//group]  (bf16, E4M3-valued)
      v_scales:   [L, NP, H, BS, D//group]
      slot_state: [L, R, NB, BS]     uint8 per-request logical (1 == valid).
      slot_bits:  [L, R, NB, BS]     uint8 in {2,4,8}.
      block_table:[R, L, NB]         int32 RAW logical -> physical block
                  (-1 == unmapped; clamped here — unmapped slots are FREE).
      buf_k:      [L, R, H, G, D]    full-precision TBQ buffer keys.
      buf_v:      [L, R, H, G, D]
      buf_len:    [R]                int32 valid buffer tokens per slot.

    Returns:
      out [L, R, H, GQ, D] f32 — FINAL attention outputs (pool and buffer
      partitions merged in-kernel; no (m, l) stats plumbing).
    """
    L, r, h, gq, d = qh.shape
    bs = k_codes.shape[3]
    nb = block_table.shape[-1]
    g = buf_k.shape[3]
    ng = k_scales.shape[-1]
    table = jnp.maximum(block_table, 0).astype(jnp.int32)
    blen = buf_len.astype(jnp.int32)
    state, bits = _metadata_blocks(slot_state, slot_bits)

    grid = (L, r, h, nb + 1)
    kern = functools.partial(_fused_kernel, group=group, blocks_per_seq=nb)

    def pool_idx(ll, rr, hh, b, bt, bl):
        return (ll, bt[rr, ll, jnp.minimum(b, nb - 1)], hh, 0, 0)

    def meta_idx(ll, rr, hh, b, bt, bl):
        return (ll, rr, jnp.minimum(b, nb - 1), 0, 0)

    def head_idx(ll, rr, hh, b, bt, bl):
        return (ll, rr, hh, 0, 0)

    out = pl.pallas_call(
        kern,
        name="ct_paged_attention_fused",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, 1, gq, d), head_idx),
                pl.BlockSpec((1, 1, 1, bs, d), pool_idx),
                pl.BlockSpec((1, 1, 1, bs, d), pool_idx),
                pl.BlockSpec((1, 1, 1, bs, ng), pool_idx),
                pl.BlockSpec((1, 1, 1, bs, ng), pool_idx),
                pl.BlockSpec((1, 1, 1, 1, bs), meta_idx),
                pl.BlockSpec((1, 1, 1, bs, 1), meta_idx),
                pl.BlockSpec((1, 1, 1, g, d), head_idx),
                pl.BlockSpec((1, 1, 1, g, d), head_idx),
            ],
            out_specs=pl.BlockSpec((1, 1, 1, gq, d), head_idx),
            scratch_shapes=[pltpu.VMEM((gq, 1), jnp.float32),
                            pltpu.VMEM((gq, 1), jnp.float32),
                            pltpu.VMEM((gq, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((L, r, h, gq, d), jnp.float32),
        interpret=interpret,
    )(table, blen, qh, k_codes, v_codes, k_scales, v_scales, state, bits,
      buf_k, buf_v)
    return out


@functools.partial(jax.jit, static_argnames=("group", "interpret"))
def ct_paged_attention_batched(qh: jax.Array, k_codes: jax.Array,
                               v_codes: jax.Array, k_scales: jax.Array,
                               v_scales: jax.Array, slot_state: jax.Array,
                               slot_bits: jax.Array, block_table: jax.Array,
                               *, group: int = 16, interpret: bool = False
                               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Paged decode attention over a SHARED quantized pool, one layer, every
    request slot in one launch.

    Args:
      qh:         [R, H, GQ, D]  queries per kv head (post-RoPE).
      k_codes:    [NP, H, BS, D] uint8 physical pool planes.
      v_codes:    [NP, H, BS, D]
      k_scales:   [NP, H, BS, D//group]  (bf16, E4M3-valued)
      v_scales:   [NP, H, BS, D//group]
      slot_state: [R, NB, BS]    uint8 per-request logical (1 == valid).
      slot_bits:  [R, NB, BS]    uint8 in {2,4,8}.
      block_table:[R, NB]        int32 RAW logical -> physical block
                  (-1 == unmapped; clamped here — unmapped slots are FREE).

    Returns:
      out [R, H, GQ, D] f32, m [R, H, GQ, 1], l [R, H, GQ, 1] flash stats
      for merging with the B_buf attention.
    """
    r, h, gq, d = qh.shape
    _, hp, bs, _ = k_codes.shape
    assert hp == h, (hp, h)
    ng = k_scales.shape[-1]
    nb = block_table.shape[-1]
    block_table = jnp.maximum(block_table, 0).astype(jnp.int32)
    state, bits = _metadata_blocks(slot_state, slot_bits)

    grid = (r, h, nb)
    kern = functools.partial(_kernel, group=group, blocks_per_seq=nb)

    def pool_idx(rr, hh, b, bt):
        return (bt[rr, b], hh, 0, 0)

    def meta_idx(rr, hh, b, bt):
        return (rr, b, 0, 0)

    def head_idx(rr, hh, b, bt):
        return (rr, hh, 0, 0)

    out, m, l = pl.pallas_call(
        kern,
        name="ct_paged_attention_batched",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, gq, d), head_idx),
                pl.BlockSpec((1, 1, bs, d), pool_idx),
                pl.BlockSpec((1, 1, bs, d), pool_idx),
                pl.BlockSpec((1, 1, bs, ng), pool_idx),
                pl.BlockSpec((1, 1, bs, ng), pool_idx),
                pl.BlockSpec((1, 1, 1, bs), meta_idx),
                pl.BlockSpec((1, 1, bs, 1), meta_idx),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, gq, d), head_idx),
                pl.BlockSpec((1, 1, gq, 1), head_idx),
                pl.BlockSpec((1, 1, gq, 1), head_idx),
            ],
            scratch_shapes=[pltpu.VMEM((gq, 1), jnp.float32),
                            pltpu.VMEM((gq, 1), jnp.float32),
                            pltpu.VMEM((gq, d), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((r, h, gq, d), jnp.float32),
            jax.ShapeDtypeStruct((r, h, gq, 1), jnp.float32),
            jax.ShapeDtypeStruct((r, h, gq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(block_table, qh, k_codes, v_codes, k_scales, v_scales, state, bits)
    return out, m, l


@functools.partial(jax.jit, static_argnames=("group", "interpret"))
def ct_paged_attention(q: jax.Array, k_codes: jax.Array, v_codes: jax.Array,
                       k_scales: jax.Array, v_scales: jax.Array,
                       slot_state: jax.Array, slot_bits: jax.Array,
                       block_table: jax.Array, *, group: int = 16,
                       interpret: bool = False
                       ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Single-request wrapper (one request+layer) over the batched kernel.

    Args:
      q:          [Hq, D]        current query (post-RoPE).
      k_codes/v_codes/k_scales/v_scales: [NP, H, BS, ...] pool planes.
      slot_state/slot_bits: [NP, BS] PHYSICAL-layout metadata (legacy
                  single-request convention: gathered through the table
                  here so the batched kernel sees the logical view).
      block_table:[NB]           int32 RAW sequence block -> physical block
                  (-1 == unmapped; clamped here).

    Returns:
      out [Hq, D] f32, m [H, Gq, 1], l [H, Gq, 1].
    """
    hq, d = q.shape
    h = k_codes.shape[1]
    gq = hq // h
    qh = q.reshape(1, h, gq, d)
    safe = jnp.maximum(block_table, 0)
    state = jnp.take(slot_state, safe, axis=0)                 # [NB, BS]
    # unmapped entries gather physical block 0 — mask its state out so -1
    # means "no tokens here" regardless of what block 0 holds
    state = jnp.where((block_table >= 0)[:, None], state, 0)[None]
    bits = jnp.take(slot_bits, safe, axis=0)[None]
    out, m, l = ct_paged_attention_batched(
        qh, k_codes, v_codes, k_scales, v_scales, state, bits,
        block_table[None], group=group, interpret=interpret)
    return out[0].reshape(hq, d), m[0], l[0]
