"""Public jit'd kernel wrappers with backend dispatch.

On TPU the Pallas kernels run compiled; on CPU (this container, and the
dry-run's 512 fake host devices) the pure-jnp oracles are used so that
``lower().compile()`` succeeds on every backend.  ``force='pallas'`` runs
kernels in interpret mode (used by the correctness tests);
``force='ref'`` forces the oracle.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import ct_cache as CC
from repro.kernels import ref as R
from repro.kernels.ct_paged_attention import (ct_paged_attention,
                                              ct_paged_attention_batched,
                                              ct_paged_attention_fused)
from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.group_quant import group_quant


def _use_pallas(force: Optional[str]) -> Tuple[bool, bool]:
    """-> (use_kernel, interpret)."""
    if force == "pallas":
        return True, jax.default_backend() != "tpu"
    if force == "ref":
        return False, False
    return jax.default_backend() == "tpu", False


def paged_decode_attention(q, k_codes, v_codes, k_scales, v_scales,
                           slot_state, slot_bits, block_table, *,
                           group: int = 16, force: Optional[str] = None):
    """CT paged attention, single request -> (out [Hq,D], m, l)."""
    use, interp = _use_pallas(force)
    if use:
        return ct_paged_attention(q, k_codes, v_codes, k_scales, v_scales,
                                  slot_state, slot_bits, block_table,
                                  group=group, interpret=interp)
    return R.ct_paged_attention_ref(q, k_codes, v_codes, k_scales, v_scales,
                                    slot_state, slot_bits, block_table,
                                    group=group)


def paged_decode_attention_batched(qh, k_codes, v_codes, k_scales, v_scales,
                                   slot_state, slot_bits, block_table, *,
                                   group: int = 16,
                                   force: Optional[str] = None):
    """Batched CT paged attention over the SHARED physical pool: one launch
    per layer for every request slot of a continuous-batching tick.

    qh [R, H, GQ, D]; planes [NP, H, BS, ...]; slot_state/slot_bits
    [R, NB, BS] logical; block_table [R, NB] RAW (-1 == unmapped; clamped
    by the entry points — their slots are FREE so the state mask zeroes
    their contribution).
    Returns (out [R, H, GQ, D], m [R, H, GQ, 1], l [R, H, GQ, 1]).
    """
    use, interp = _use_pallas(force)
    if use:
        return ct_paged_attention_batched(
            qh, k_codes, v_codes, k_scales, v_scales, slot_state, slot_bits,
            block_table, group=group, interpret=interp)
    return R.ct_paged_attention_batched_ref(
        qh, k_codes, v_codes, k_scales, v_scales, slot_state, slot_bits,
        block_table, group=group)


def paged_decode_attention_fused(qh, k_codes, v_codes, k_scales, v_scales,
                                 slot_state, slot_bits, block_table,
                                 buf_k, buf_v, buf_len, *, group: int = 16,
                                 force: Optional[str] = None):
    """A whole decode tick's attention in ONE kernel launch: every layer and
    request slot, quantized pool ∪ fp TBQ buffer merged in VMEM.

    qh [L, R, H, GQ, D]; planes [L, NP, H, BS, ...]; slot_state/slot_bits
    [L, R, NB, BS]; block_table [R, L, NB] RAW (-1 accepted); buf_k/buf_v
    [L, R, H, G, D]; buf_len [R].  Returns FINAL out [L, R, H, GQ, D].
    """
    use, interp = _use_pallas(force)
    if use:
        return ct_paged_attention_fused(
            qh, k_codes, v_codes, k_scales, v_scales, slot_state, slot_bits,
            block_table, buf_k, buf_v, buf_len, group=group,
            interpret=interp)
    return R.ct_paged_attention_fused_ref(
        qh, k_codes, v_codes, k_scales, v_scales, slot_state, slot_bits,
        block_table, buf_k, buf_v, buf_len, group=group)


# ---------------------------------------------------------------------------
# per-shard launch plumbing (tensor-parallel serving over the KV-head axis)
# ---------------------------------------------------------------------------
# Inside the engine's ``shard_map``, each device launches the SAME fused
# kernel over its contiguous slice of KV heads: no kernel reads across
# heads (a fused grid step loops over its heads, each with its own
# scores and values), so a per-shard launch over H/n heads computes
# exactly the corresponding slice of the single-device launch.  This slice (going in) plus
# ``core.ct_cache.gather_heads`` (attention outputs coming back out) are
# the only sharding the kernel entry points ever see — pure data
# movement; the per-head math is untouched, keeping sharded runs
# bit-identical.


def local_heads(x: jax.Array, axis: int, axis_name: str,
                num_shards: int) -> jax.Array:
    """This shard's contiguous head range along ``axis`` (call only inside
    ``shard_map``; the head dim must divide by ``num_shards``).  Works for
    both KV-head axes and query-head axes — queries are laid out kv-head-
    major (``Hq = H * gq``), so a contiguous Hq/n slice is exactly the
    queries of the shard's kv heads."""
    size = x.shape[axis] // num_shards
    i = jax.lax.axis_index(axis_name)
    return jax.lax.dynamic_slice_in_dim(x, i * size, size, axis)


def count_pallas_launches(jaxpr, while_trips: int = 1) -> int:
    """Compatibility shim for the historical launch counter — the walker
    now lives in ``repro.analysis.jaxpr_audit`` (scan bodies multiplied
    by trip count, ``while`` bodies by ``while_trips``, cond launches
    counted once).

    CAVEAT kept for compatibility: ``cond`` branches contribute their
    MAXIMUM, which silently hides branch-count divergence (a branch that
    dispatches 2 launches against a branch that dispatches 1 reads as
    "2").  New audits should use ``repro.analysis.census_of``, which
    records per-branch counts and whose contracts reject divergent
    branches, or go through ``repro.analysis.audit_engine`` entirely.
    """
    from repro.analysis.jaxpr_audit import count_launches
    return count_launches(jaxpr, while_trips=while_trips)


def buffer_attention(q, buf_k, buf_v, buf_len):
    """Flash stats over the full-precision TBQ buffer (<= g tokens).

    q [Hq,D]; buf_k/buf_v [G,H,D].  Returns (out, m, l) shaped like the
    paged kernel outputs so they merge directly.
    """
    hq, d = q.shape
    g, h, _ = buf_k.shape
    gq = hq // h
    valid = jnp.arange(g) < buf_len
    qh = q.reshape(h, gq, d).astype(jnp.float32)
    s = jnp.einsum("hgd,nhd->hgn", qh,
                   buf_k.astype(jnp.float32)) / jnp.sqrt(float(d))
    s = jnp.where(valid[None, None, :], s, R.NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = jnp.where(valid[None, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("hgn,nhd->hgd", p / jnp.maximum(l, 1e-30),
                     buf_v.astype(jnp.float32))
    return out.reshape(hq, d), m, l


def thinkv_decode_attention(dims: CC.CacheDims, cache: CC.CTCache,
                            view: CC.PoolView, q: jax.Array, layer: int, *,
                            force: Optional[str] = None) -> jax.Array:
    """Full ThinKV decode attention for one layer: paged pool ∪ B_buf.

    Single-request form: the request's paged view IS its physical pool, so
    the block table is the identity (the engine's shared-pool path goes
    through :func:`paged_decode_attention_batched` with real tables).
    """
    shp = (dims.NB, dims.BS)
    table = jnp.arange(dims.NB, dtype=jnp.int32)
    out_p, m_p, l_p = paged_decode_attention(
        q,
        view.k_codes[layer], view.v_codes[layer],
        view.k_scales[layer], view.v_scales[layer],
        cache.slot_state[layer].reshape(shp),
        cache.slot_bits[layer].reshape(shp),
        table, group=16, force=force)
    out_b, m_b, l_b = buffer_attention(q, cache.buf_k[layer],
                                       cache.buf_v[layer], cache.buf_len)
    return R.merge_flash_ref(out_p, m_p, l_p, out_b, m_b, l_b)


def tbq_group_quant(x, bits: int, group: int = 16, *,
                    force: Optional[str] = None):
    """Group quantization -> (codes, scales).  x: [N, D]."""
    use, interp = _use_pallas(force)
    if use:
        return group_quant(x, bits, group, interpret=interp)
    from repro.core import quantization as Q
    codes, scales = Q.quantize_group(x, bits, group)
    return codes, scales.astype(jnp.bfloat16)


def _check_prefill_tile(s_len: int) -> None:
    """The kernel path never drops to the oracle in silence: a chunk that
    is not a whole number of 128-token tiles is a caller bug."""
    if s_len % 128:
        raise ValueError(
            f"flash_prefill needs a 128-multiple chunk, got {s_len} tokens "
            f"(padded chunks pass kv_valid and take the reference path)")


def prefill_attention_stats(q, k, v, *, causal: bool = True, window: int = 0,
                            kv_valid=None, force: Optional[str] = None):
    """Prefill attention with per-query flash stats (m, l) [S, Hq, 1] —
    the chunk partition of the chunked-prefill path; merged against the
    paged-pool partition by the engine.  ``kv_valid`` masks padded kv
    positions: a padded chunk (the engine's g-sized tail) always takes
    the reference oracle.  An unpadded chunk on the kernel path must be
    a 128-multiple, or this raises.
    """
    use, interp = _use_pallas(force)
    if use and kv_valid is None:
        _check_prefill_tile(q.shape[0])
        return flash_prefill(q, k, v, causal=causal, window=window,
                             interpret=interp, return_stats=True)
    return R.flash_prefill_stats_ref(q, k, v, causal=causal, window=window,
                                     kv_valid=kv_valid)
