"""Ring (context-parallel) causal flash attention over a mesh axis.

Motivation (EXPERIMENTS.md §Perf, qwen2/llama4): when num_heads is not
divisible by the `model` axis (28 % 16, 40 % 16), GSPMD cannot head-shard
attention and falls back to replicating activations / all-gathering around
every attention op.  Ring attention sidesteps heads entirely:

* activations shard over the SEQUENCE on `model`;
* each device holds its q chunk [B, S/m, Hq, d] and rotates K/V chunks
  around the ring with `ppermute`, flash-accumulating (m, l, acc);
* causality is enforced per (q-chunk, kv-chunk) pair from global offsets —
  fully-masked pairs still rotate (uniform schedule) but contribute zeros;
* communication per layer is (m-1)/m · |K,V| of point-to-point traffic that
  overlaps chunk compute (the classic ring schedule), vs the full-activation
  all-gathers GSPMD was inserting.

Used by the train/prefill attention path when REPRO_RING_ATTN=1 and the
sequence divides the `model` axis (causal, non-windowed only); equivalence
vs dense attention is tested on an 8-device mesh.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def _flash_chunk(q, k, v, mask, m_prev, l_prev, acc):
    """One (q-chunk x kv-chunk) flash update.  q [B,Sq,H,G,d]; k/v
    [B,Sk,H,d]; mask [Sq,Sk] bool."""
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                   preferred_element_type=jnp.float32)
    s = s / jnp.sqrt(float(q.shape[-1]))
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    p = jnp.where(mask[None, None, None], p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1)
    acc = acc * corr[..., None] + jnp.einsum(
        "bhgqk,bkhd->bhgqd", p, v, preferred_element_type=jnp.float32)
    return m_new, l_new, acc


def ring_attention(q, k, v, mesh, axis: str = "model"):
    """q [B,S,Hq,d], k/v [B,S,Hkv,d] (S sharded over ``axis``) -> [B,S,Hq,d].

    Causal.  GQA handled by grouping q heads over kv heads.
    """
    b, s_glob, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    m = mesh.shape[axis]

    def local(ql, kl, vl):
        idx = jax.lax.axis_index(axis)
        size = m          # static mesh axis size (jax.lax has no axis_size)
        bl, sq = ql.shape[0], ql.shape[1]
        qh = ql.reshape(bl, sq, hkv, g, d).astype(jnp.float32)
        rows = jnp.arange(sq)

        m_acc = jnp.full((bl, hkv, g, sq), NEG_INF, jnp.float32)
        l_acc = jnp.zeros((bl, hkv, g, sq), jnp.float32)
        acc = jnp.zeros((bl, hkv, g, sq, d), jnp.float32)

        perm = [(i, (i - 1) % size) for i in range(size)]
        kv = (kl.astype(jnp.float32), vl.astype(jnp.float32))

        def ring_step(step, carry):
            m_a, l_a, acc_a, (kc, vc) = carry
            src = (idx + step) % size            # whose chunk we hold now
            q_off = idx * sq
            k_off = src * sq
            mask = (q_off + rows)[:, None] >= (k_off + rows)[None, :]
            m_a, l_a, acc_a = _flash_chunk(qh, kc, vc, mask, m_a, l_a,
                                           acc_a)
            kc = jax.lax.ppermute(kc, axis, perm)
            vc = jax.lax.ppermute(vc, axis, perm)
            return m_a, l_a, acc_a, (kc, vc)

        m_a, l_a, acc, _ = jax.lax.fori_loop(
            0, size, ring_step, (m_acc, l_acc, acc, kv))
        out = acc / jnp.maximum(l_a, 1e-30)[..., None]
        # [B,H,G,Sq,d] -> [B,Sq,Hq,d]
        return out.transpose(0, 3, 1, 2, 4).reshape(bl, sq, hq, d).astype(
            q.dtype)

    # batch stays sharded over the DP axes; only `axis` participates in the
    # ring (without this the batch replicates inside the shard_map — a
    # measured 8x compute/memory blowup, §Perf ring iteration 1)
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names) or None
    bspec = dp if (dp and b % _axes_size(mesh, dp) == 0) else None
    spec = P(bspec, axis, None, None)
    return shard_map(
        local, mesh=mesh,
        in_specs=(spec,) * 3,
        out_specs=spec,
        check_vma=False)(q, k, v)


def _axes_size(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n
