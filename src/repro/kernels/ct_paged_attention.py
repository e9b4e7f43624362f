"""CT paged decode-attention Pallas TPU kernels (paper Sec. 5 'Continuous
Thinking', adapted per DESIGN.md Sec. 3).

The FUSED entry point (``ct_paged_attention_fused``) serves a whole
continuous-batching decode tick in ONE launch: the grid is
``(L, R, C + 1)`` — a leading layer axis over the pool planes (which
already carry ``[L, NP, H, BS, ...]``), then request slots, then the
slot's block walk in ``C = ceil(NB / P)`` chunks of ``P`` pages.  One
grid step covers every kv head of ``P`` pages: the pool planes stay in
HBM and each page's ``[H, BS, ...]`` codes and scales arrive by one async
copy per plane, through the scalar-prefetched block table, double-
buffered so chunk ``c + 1`` is in flight while chunk ``c`` computes.
The wrapper orders each ``(request, layer)`` table row mapped-first and
counts its mapped pages, so only mapped pages are copied and computed
and a step past the last mapped chunk does nothing.  ``P`` follows from
the shapes (``pages_per_step``: the largest power of two whose chunk fits
a fixed VMEM budget).  The final step attends the full-precision TBQ
buffer ``B_buf`` for the same ``(l, r)``, so the ``(m, l)`` flash-merge
between the quantized pool and the buffer happens in VMEM scratch — the
kernel returns FINAL outputs, no stats plumbing back to XLA.  This
amortizes launch overhead over ``L`` and removes the per-layer XLA merge
einsum, the two linear-in-``L`` costs of the per-layer launch scheme.

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
  sentinels (their slots are FREE in the metadata); the per-page kernels
  clamp them internally and let the state mask zero their contribution,
  the fused kernel never fetches them — callers never pre-clamp;
* flash accumulation state (m, l, acc) lives in VMEM scratch across the
  sequential block grid dimension.

The per-layer batched entry point (``ct_paged_attention_batched``) remains
for the chunked-prefill frozen-pool partition (its ``(m, l)`` stats merge
against the intra-chunk flash partition) and for tests; the single-request
wrapper remains for the single-sequence controller.  The query-group axis
``GQ`` is ``Hq // H`` for decode and ``chunk * Hq // H`` for chunked
prefill (every chunk token attends the same frozen pool, so chunk queries
fold into the q-group axis).

PER-SHARD LAUNCHES (tensor-parallel serving): no kernel reads across
the ``H`` axis — each head's scores and values stay that head's — so the
serving engine's ``shard_map`` simply calls these entry points with the
head axes of queries, planes, and buffers sliced to the shard's
``H / num_shards`` local heads (see ``kernels.ops.local_heads``).  The
per-shard launch computes the exact corresponding slice of the full
launch (the fused grid keeps its shape; each step loops over fewer
heads, and ``P`` may grow), and the fused tick stays ONE launch per
shard.  The head count has no tiling constraint, so any
``H % num_shards == 0`` split compiles unchanged.

Tiling: planes are ``[..., H, BS, D]``, so one block of one head is a
(block_size=16, head_dim=128) page; codes are uint8, scales a bf16 (16,
D/g) page.  The per-page batched kernel reads per-slot metadata as
``[..., 1, BS]`` (state) and ``[..., BS, 1]`` (bits) blocks, one per
page; the fused kernel reads one such block per chunk (``[1, P * BS]``
and ``[P * BS, 1]``), cast to int32 in-kernel — Mosaic accepts neither a
dynamic sublane offset into a uint8 tile nor 1-D uint8 vectors.  A
manual copy may not slice a plane whose last dim is narrower than the
128-lane tile, so the fused wrapper hands the kernel each page's K and
V scales as one f32 row per head (``[L, NP, H, 2 * BS * D/g]``, a third
plane beside the two code planes), widened per token in-kernel by a 0/1
matmul.  The kernels compile for a v5e at block_size
16 (``tests/test_tpu_compile.py``).

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


def _code_values(codes, bits):
    """Code decode to f32 values before the group scale: ``codes`` any
    integer dtype (cast to int32), ``bits`` broadcastable to it with the
    per-slot width in {2, 4, 8}."""
    c = codes.astype(jnp.int32)
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
    return jnp.where(bits == 2, v2, jnp.where(bits == 4, v4, v8))


def _widen(scales, expand):
    """``scales @ expand`` for a 0/1 ``expand``: each output sums one
    bf16 scale, so the f32 result is exact."""
    return jax.lax.dot_general(
        scales.astype(jnp.bfloat16), expand.astype(jnp.bfloat16),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def _decode_codes(codes_u8, bits, scales, group: int):
    """Fused in-VMEM dequant of one page: [BS, D] uint8 codes -> f32.

    ``bits`` [BS, 1] int32 is the per-slot width in {2, 4, 8};
    ``scales`` [BS, D//group] holds E4M3-valued group scales.  The scales
    widen to [BS, D] through a 0/1 [D//group, D] matmul: Mosaic cannot
    reshape the lane axis into (D//group, group), and a bf16 x {0, 1}
    product summed once is exact."""
    vals = _code_values(codes_u8, bits)
    ng, d = scales.shape[-1], vals.shape[-1]
    expand = (jax.lax.broadcasted_iota(jnp.int32, (ng, d), 1) // group ==
              jax.lax.broadcasted_iota(jnp.int32, (ng, d), 0))
    return vals * _widen(scales, expand)


def _page_row_scales(scales, bs: int, d: int, group: int):
    """Group scales of ``P`` pages, one page per row (``[P, BS * ng]``,
    token-major), widened to one row per token: ``[P * BS, D]`` f32.

    Each page row repeats over its BS token rows (a sublane broadcast),
    every lane but the token's own ``ng`` groups is zeroed, and a 0/1
    ``[BS * ng, D]`` matmul widens the groups — exact, as in
    :func:`_decode_codes`, with no lane-axis reshape."""
    p, w = scales.shape
    ng = w // bs
    rep = jnp.broadcast_to(scales[:, None, :], (p, bs, w)).reshape(p * bs, w)
    token = jax.lax.broadcasted_iota(jnp.int32, (p * bs, w), 0) % bs
    lane = jax.lax.broadcasted_iota(jnp.int32, (p * bs, w), 1)
    rep = jnp.where(lane // ng == token, rep, 0.0)
    expand = (jax.lax.broadcasted_iota(jnp.int32, (w, d), 1) // group ==
              jax.lax.broadcasted_iota(jnp.int32, (w, d), 0) % ng)
    return _widen(rep, expand)


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


def _fused_kernel(bt_ref, cnt_ref, blen_ref, q_ref, kc_hbm, vc_hbm, sc_hbm,
                  state_ref, bits_ref, bk_ref, bv_ref, o_ref, kc_buf, vc_buf,
                  sc_buf, sem, m_ref, l_ref, acc_ref, *,
                  group: int, pages: int, chunks: int, blocks_per_seq: int):
    """One (layer, request) flash pass over every kv head: the slot's
    mapped pool pages in chunks of ``pages``, then the fp TBQ buffer as
    the final grid step, final output from scratch.

    ``bt_ref`` holds each ``(request, layer)`` table row mapped-first and
    ``cnt_ref`` its mapped count, so chunk ``c`` covers row entries
    ``[c * pages, (c + 1) * pages)`` and copies only those below the
    count.  Chunk ``c + 1``'s copies start before chunk ``c`` is computed
    (buffer slot ``c % 2``); a step past the last mapped chunk does
    nothing."""
    ll, rr, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    row = rr * pl.num_programs(0) + ll
    count = cnt_ref[row]
    n_chunks = (count + pages - 1) // pages
    heads, bs = kc_buf.shape[2], kc_buf.shape[3]
    planes = ((kc_hbm, kc_buf), (vc_hbm, vc_buf), (sc_hbm, sc_buf))
    w = sc_buf.shape[-1] // 2                 # one plane's scales per row

    def each_copy(chunk, op):
        """``op`` on the async copy of every plane of every mapped page of
        ``chunk``: one copy per plane and page moves all heads."""
        slot = jax.lax.rem(chunk, 2)
        first = chunk * pages

        def page(p, carry):
            phys = bt_ref[row * blocks_per_seq + first + p]
            for hbm, buf in planes:
                op(pltpu.make_async_copy(hbm.at[ll, phys], buf.at[slot, p],
                                         sem.at[slot]))
            return carry

        jax.lax.fori_loop(0, jnp.minimum(pages, count - first), page, 0)

    @pl.when(c == 0)
    def _init():
        _init_scratch(m_ref, l_ref, acc_ref)

        @pl.when(n_chunks > 0)
        def _first():
            each_copy(c, lambda cp: cp.start())

    @pl.when(c < n_chunks)
    def _pool_chunk():
        @pl.when(c + 1 < n_chunks)
        def _next():
            each_copy(c + 1, lambda cp: cp.start())

        each_copy(c, lambda cp: cp.wait())
        slot = jax.lax.rem(c, 2)
        n = pages * bs
        # pages past the mapped count were not fetched: their buffer rows
        # are stale (possibly NaN bit patterns), so they leave both the
        # scores and the values
        left = count - c * pages
        fetched_col = (jax.lax.broadcasted_iota(jnp.int32, (1, n), 1) // bs
                       < left)
        fetched_row = (jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0) // bs
                       < left)
        valid = (state_ref[0, 0, 0].astype(jnp.int32) == VALID) & fetched_col
        bits = bits_ref[0, 0, 0].astype(jnp.int32)             # [n, 1]

        def dequant(codes_buf, scales, h):
            codes = codes_buf[slot, :, h].astype(jnp.int32)  # [P, BS, D]
            codes = codes.reshape(n, codes.shape[-1])
            return _code_values(codes, bits) * _page_row_scales(
                scales, bs, codes.shape[-1], group)

        def head(h, carry):
            q = q_ref[0, 0, h].astype(jnp.float32)             # [GQ, D]
            scales = sc_buf[slot, :, h]                        # [P, 2W]
            k = dequant(kc_buf, scales[:, :w], h)              # [n, D]
            v = dequant(vc_buf, scales[:, w:], h)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            _flash_update(m_ref.at[h], l_ref.at[h], acc_ref.at[h],
                          s * (1.0 / (q.shape[-1] ** 0.5)), valid,
                          jnp.where(fetched_row, v, 0.0))
            return carry

        jax.lax.fori_loop(0, heads, head, 0)

    @pl.when(c == chunks)
    def _buffer_and_final():
        def head(h, carry):
            q = q_ref[0, 0, h].astype(jnp.float32)             # [GQ, D]
            bk = bk_ref[0, 0, h].astype(jnp.float32)           # [G, D]
            bv = bv_ref[0, 0, h].astype(jnp.float32)
            s = jax.lax.dot_general(q, bk, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            pos = jax.lax.broadcasted_iota(jnp.int32, (1, bk.shape[0]), 1)
            _flash_update(m_ref.at[h], l_ref.at[h], acc_ref.at[h],
                          s * (1.0 / (q.shape[-1] ** 0.5)),
                          pos < blen_ref[rr], bv)
            o_ref[0, 0, h] = acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)
            return carry

        jax.lax.fori_loop(0, heads, head, 0)


def _metadata_blocks(slot_state, slot_bits):
    """[..., NB, BS] uint8 metadata -> state [..., NB, 1, BS] and bits
    [..., NB, BS, 1] (free reshapes).  Each page then reads its own
    block, whose last two dims equal the array's: the tiling check
    passes at BS=16, no dynamic sublane offset into a uint8 tile is
    needed, and no 1-D vector reaches Mosaic."""
    return slot_state[..., None, :], slot_bits[..., None]


#: VMEM a grid step of the fused kernel may fill with page chunks: its
#: double-buffered codes and scales plus the f32 keys and values its
#: pages dequantize to.  Well inside the ~16 MB scoped default.
CHUNK_VMEM_BYTES = 2 * 1024 * 1024


def pages_per_step(nb: int, heads: int, bs: int, d: int, ng: int) -> int:
    """Pages one grid step of :func:`ct_paged_attention_fused` fetches:
    the largest power of two, at most ``nb``, whose chunk fits
    :data:`CHUNK_VMEM_BYTES` (a power of two keeps a chunk's ``P * BS``
    keys on whole 128-lane vregs at BS 16)."""
    page = heads * bs * (2 * d + 2 * 4 * ng)      # K, V codes + f32 scales
    temps = 2 * heads * bs * d * 4                # f32 K and V, every head
    p = 1
    while 2 * p <= nb and 2 * p * (2 * page + temps) <= CHUNK_VMEM_BYTES:
        p *= 2
    return p


def _mapped_first(block_table, slot_state, slot_bits):
    """Each ``(request, layer)`` table row with its mapped entries first
    (stable, so their order is kept) and its metadata rows permuted
    alike: ``(table [R, L, NB], counts [R, L], state, bits)``.  Softmax
    over a set does not depend on its order."""
    table = block_table.astype(jnp.int32)
    order = jnp.argsort(table < 0, axis=-1, stable=True)
    table = jnp.take_along_axis(table, order, axis=-1)
    perm = jnp.swapaxes(order, 0, 1)[..., None]            # [L, R, NB, 1]
    return (table, jnp.sum(table >= 0, axis=-1, dtype=jnp.int32),
            jnp.take_along_axis(slot_state, perm, axis=2),
            jnp.take_along_axis(slot_bits, perm, axis=2))


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
                  (-1 == unmapped; never fetched — unmapped slots are FREE).
      buf_k:      [L, R, H, G, D]    full-precision TBQ buffer keys.
      buf_v:      [L, R, H, G, D]
      buf_len:    [R]                int32 valid buffer tokens per slot.

    Returns:
      out [L, R, H, GQ, D] f32 — FINAL attention outputs (pool and buffer
      partitions merged in-kernel; no (m, l) stats plumbing).
    """
    _, _, h, _, d = qh.shape
    pages = pages_per_step(block_table.shape[-1], h, k_codes.shape[3], d,
                           k_scales.shape[-1])
    return _fused_launch(qh, k_codes, v_codes, k_scales, v_scales,
                         slot_state, slot_bits, block_table, buf_k, buf_v,
                         buf_len, group=group, pages=pages,
                         interpret=interpret)


def _fused_launch(qh, k_codes, v_codes, k_scales, v_scales, slot_state,
                  slot_bits, block_table, buf_k, buf_v, buf_len, *,
                  group: int, pages: int, interpret: bool) -> jax.Array:
    """:func:`ct_paged_attention_fused` at a given chunk of ``pages``."""
    L, r, h, gq, d = qh.shape
    bs = k_codes.shape[3]
    nb = block_table.shape[-1]
    g = buf_k.shape[3]
    ng = k_scales.shape[-1]
    chunks = -(-nb // pages)
    table, counts, state, bits = _mapped_first(block_table, slot_state,
                                               slot_bits)
    # a page's K then V scales as one row per head ([L, NP, H, 2 * BS *
    # ng] f32): a copy may only slice a plane whose last dim fills the
    # 128-lane tile, which [BS, ng] does not; f32 lets the kernel index
    # the head row; one plane for both saves a copy per page
    scale_rows = jnp.concatenate(
        [x.reshape(x.shape[:3] + (bs * ng,)) for x in (k_scales, v_scales)],
        axis=-1).astype(jnp.float32)
    # one metadata block per chunk: state [1, P*BS], bits [P*BS, 1]
    pad = ((0, 0), (0, 0), (0, chunks * pages - nb), (0, 0))
    state = jnp.pad(state, pad).reshape(L, r, chunks, 1, pages * bs)
    bits = jnp.pad(bits, pad).reshape(L, r, chunks, pages * bs, 1)

    kern = functools.partial(_fused_kernel, group=group, pages=pages,
                             chunks=chunks, blocks_per_seq=nb)

    def meta_idx(ll, rr, c, bt, cnt, bl):
        # a step past the last mapped chunk keeps the block it has, so
        # the pipeline fetches nothing for it
        last = jnp.maximum((cnt[rr * L + ll] + pages - 1) // pages - 1, 0)
        return (ll, rr, jnp.minimum(c, last), 0, 0)

    def slot_idx(ll, rr, c, bt, cnt, bl):
        return (ll, rr, 0, 0, 0)

    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    out = pl.pallas_call(
        kern,
        name="ct_paged_attention_fused",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(L, r, chunks + 1),
            in_specs=[
                pl.BlockSpec((1, 1, h, gq, d), slot_idx),
                hbm, hbm, hbm,
                pl.BlockSpec((1, 1, 1, 1, pages * bs), meta_idx),
                pl.BlockSpec((1, 1, 1, pages * bs, 1), meta_idx),
                pl.BlockSpec((1, 1, h, g, d), slot_idx),
                pl.BlockSpec((1, 1, h, g, d), slot_idx),
            ],
            out_specs=pl.BlockSpec((1, 1, h, gq, d), slot_idx),
            scratch_shapes=[pltpu.VMEM((2, pages, h, bs, d), k_codes.dtype),
                            pltpu.VMEM((2, pages, h, bs, d), v_codes.dtype),
                            pltpu.VMEM((2, pages, h, 2 * bs * ng),
                                       jnp.float32),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.VMEM((h, gq, 1), jnp.float32),
                            pltpu.VMEM((h, gq, 1), jnp.float32),
                            pltpu.VMEM((h, gq, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((L, r, h, gq, d), jnp.float32),
        interpret=interpret,
    )(table.reshape(-1), counts.reshape(-1), buf_len.astype(jnp.int32), qh,
      k_codes, v_codes, scale_rows, state, bits, buf_k, buf_v)
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
