"""ThinKV controller — the generation-loop logic of paper Listing 1.

Couples the CT cache with the model's decode step:

    for each generated token:
        q, k, v = project_qkv(h)
        cache, pool = append_token(cache, pool, k, v)  # TBQ buffer / commit
        h = attention(q, cache, pool)                  # CT paged attention
        if step % tau == 0:
            s = sparsity over L* layers                # thought refresh
            cache = refresh(cache, pool, s)            # classify + TBE

State is split per the paged refactor: :class:`~repro.core.ct_cache.CTCache`
carries metadata + the TBQ buffer, :class:`~repro.core.ct_cache.PoolView`
carries the quantized planes in paged ``[L, NB, H, BS, ...]`` layout — the
layout the Pallas kernel (`repro.kernels.ct_paged_attention`) streams.
`decode_attention_ref` here is the pure-jnp oracle the kernel is validated
against and the CPU fallback.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ThinKVConfig
from repro.core import ct_cache as CC
from repro.core.thoughts import row_sparsity

NEG_INF = -1e30


def _gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q [Hq,D] x k [N,H,D] -> scores [H, Hq//H, N]."""
    hq, d = q.shape
    n, h, _ = k.shape
    qg = q.reshape(h, hq // h, d)
    return jnp.einsum("hgd,nhd->hgn", qg, k) / jnp.sqrt(float(d))


def decode_attention_ref(dims: CC.CacheDims, cache: CC.CTCache,
                         view: CC.PoolView, q: jax.Array, layer: int,
                         return_probs: bool = False):
    """Reference decode attention for one layer over (paged cache ∪ buffer).

    Args:
      q: [Hq, D] query for the current token (RoPE already applied).
    Returns: out [Hq, D] (and optionally probs + validity for stats).
    """
    k_c, v_c, valid_c = CC.dequant_layer(dims, cache, view, layer)
    buf_valid = jnp.arange(dims.G) < cache.buf_len
    k = jnp.concatenate([k_c, cache.buf_k[layer].astype(jnp.float32)], 0)
    v = jnp.concatenate([v_c, cache.buf_v[layer].astype(jnp.float32)], 0)
    valid = jnp.concatenate([valid_c, buf_valid], 0)

    s = _gqa_scores(q, k)                                 # [H,G,N]
    s = jnp.where(valid[None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(valid[None, None, :], p, 0.0)
    out = jnp.einsum("hgn,nhd->hgd", p, v).reshape(q.shape)
    if return_probs:
        return out, p, valid
    return out


def layer_sparsity(dims: CC.CacheDims, cache: CC.CTCache, view: CC.PoolView,
                   q: jax.Array, layer: int) -> jax.Array:
    """Decode-step sparsity for one calibrated layer (paper App. C.2: GQA
    max-pool over the group, renormalize, measure)."""
    _, p, valid = decode_attention_ref(dims, cache, view, q, layer,
                                       return_probs=True)
    pooled = jnp.max(p, axis=1)                           # [H, N] maxpool
    pooled = jnp.where(valid[None, :], pooled, NEG_INF)
    renorm = jax.nn.softmax(jnp.log(jnp.maximum(pooled, 1e-30)), axis=-1)
    vb = jnp.broadcast_to(valid[None, :], renorm.shape)
    return jnp.mean(row_sparsity(renorm, vb))


def step_token(cfg: ThinKVConfig, dims: CC.CacheDims, cache: CC.CTCache,
               view: CC.PoolView, k_t: jax.Array, v_t: jax.Array,
               sparsity: Optional[jax.Array] = None, policy=None
               ) -> Tuple[CC.CTCache, CC.PoolView]:
    """One generation step's cache updates: append (+commit), and at tau
    boundaries run the thought refresh with the supplied sparsity."""
    cache, view = CC.append_token(cfg, dims, cache, view, k_t, v_t,
                                  policy=policy)
    if sparsity is None:
        return cache, view
    at_refresh = (cache.num_tokens % cfg.refresh_interval) == 0
    cache = jax.lax.cond(
        at_refresh,
        lambda c: CC.refresh(cfg, dims, c, view, sparsity, policy=policy),
        lambda c: c, cache)
    return cache, view


# ---------------------------------------------------------------------------
# Compression accounting (paper Sec. 2 memory model)
# ---------------------------------------------------------------------------

def compression_ratio(cfg: ThinKVConfig, dims: CC.CacheDims,
                      cache: CC.CTCache, full_tokens: jax.Array) -> dict:
    """ThinKV footprint vs an uncompressed bf16 cache of ``full_tokens``."""
    stats = CC.memory_stats(cfg, dims, cache)
    # FullKV: K+V bf16, all layers
    full_bytes = full_tokens * 2 * 2 * dims.H * dims.D * dims.L
    phys = jnp.sum(stats["physical_bytes"]).astype(jnp.float32)
    # metadata/buffer bytes from the shared accounting next to the field
    # definitions (CC.metadata_bytes is pinned against live array nbytes
    # in tests — the hand-written constants that used to live here had
    # drifted: they omitted seg_type/seg_level and the int32 scalars)
    meta = CC.metadata_bytes(dims)
    buf = CC.buffer_bytes(dims)
    ratio = (phys + meta + buf) / jnp.maximum(full_bytes, 1)
    return {**stats, "footprint_frac": ratio, "full_bytes": full_bytes}
