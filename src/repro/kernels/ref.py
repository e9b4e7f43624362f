"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract).

Each function mirrors its kernel's exact interface so tests can
``assert_allclose(kernel(...), ref(...))`` across shape/dtype sweeps.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import quantization as Q
from repro.core.ct_cache import page_tokens

NEG_INF = -1e30
VALID = 1


def ct_paged_attention_batched_ref(qh, k_codes, v_codes, k_scales, v_scales,
                                   slot_state, slot_bits, block_table, *,
                                   group: int = 16
                                   ) -> Tuple[jax.Array, jax.Array,
                                              jax.Array]:
    """Oracle for
    :func:`repro.kernels.ct_paged_attention.ct_paged_attention_batched`.

    qh [R, H, GQ, D]; code/scale planes [NP, H, BS, ...] (shared pool);
    slot_state/slot_bits [R, NB, BS] logical; block_table [R, NB] RAW
    (-1 == unmapped; clamped here — unmapped slots are FREE).
    """
    r, h, gq, d = qh.shape
    block_table = jnp.maximum(block_table, 0)

    def one(qh_r, state_r, bits_r, table_r):
        take = lambda a: page_tokens(jnp.take(a, table_r, axis=0))
        kc, vc = take(k_codes), take(v_codes)
        ks, vs = take(k_scales), take(v_scales)
        bits_n = bits_r.reshape(-1).astype(jnp.int32)[:, None, None]
        k = Q.dequantize_by_bitcode(kc, ks.astype(jnp.float32),
                                    bits_n, g=group)       # [n,H,D]
        v = Q.dequantize_by_bitcode(vc, vs.astype(jnp.float32),
                                    bits_n, g=group)
        valid = state_r.reshape(-1) == VALID                # [n]
        s = jnp.einsum("hgd,nhd->hgn", qh_r.astype(jnp.float32), k)
        s = s / jnp.sqrt(float(d))
        s = jnp.where(valid[None, None, :], s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        p = jnp.where(valid[None, None, :], p, 0.0)
        l = jnp.sum(p, axis=-1, keepdims=True)
        out = jnp.einsum("hgn,nhd->hgd", p / jnp.maximum(l, 1e-30), v)
        return out, m, l

    return jax.vmap(one)(qh, slot_state, slot_bits, block_table)


def ct_paged_attention_ref(q, k_codes, v_codes, k_scales, v_scales,
                           slot_state, slot_bits, block_table, *,
                           group: int = 16
                           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Oracle for :func:`repro.kernels.ct_paged_attention.ct_paged_attention`
    (single request; slot_state/slot_bits in PHYSICAL [NP, BS] layout)."""
    hq, d = q.shape
    h = k_codes.shape[1]
    gq = hq // h
    qh = q.reshape(1, h, gq, d)
    safe = jnp.maximum(block_table, 0)
    state = jnp.take(slot_state, safe, axis=0)
    # unmapped entries gather physical block 0 — mask its state out so -1
    # means "no tokens here" regardless of what block 0 holds
    state = jnp.where((block_table >= 0)[:, None], state, 0)[None]
    bits = jnp.take(slot_bits, safe, axis=0)[None]
    out, m, l = ct_paged_attention_batched_ref(
        qh, k_codes, v_codes, k_scales, v_scales, state, bits,
        block_table[None], group=group)
    return out[0].reshape(hq, d), m[0], l[0]


def buffer_attention_batched_ref(qh, buf_k, buf_v, buf_len
                                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Flash stats over the full-precision TBQ buffer, every request slot.

    qh [R, H, GQ, D]; buf_k/buf_v [R, G, H, D]; buf_len [R].
    Returns (out [R, H, GQ, D], m [R, H, GQ, 1], l [R, H, GQ, 1]).
    """
    d = qh.shape[-1]
    g = buf_k.shape[1]

    def one(qh_r, bk, bv, n):
        valid = jnp.arange(g) < n
        s = jnp.einsum("hgd,nhd->hgn", qh_r.astype(jnp.float32),
                       bk.astype(jnp.float32)) / jnp.sqrt(float(d))
        s = jnp.where(valid[None, None, :], s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        p = jnp.where(valid[None, None, :], p, 0.0)
        l = jnp.sum(p, axis=-1, keepdims=True)
        out = jnp.einsum("hgn,nhd->hgd", p / jnp.maximum(l, 1e-30),
                         bv.astype(jnp.float32))
        return out, m, l

    return jax.vmap(one)(qh, buf_k, buf_v, buf_len)


def ct_paged_attention_fused_ref(qh, k_codes, v_codes, k_scales, v_scales,
                                 slot_state, slot_bits, block_table,
                                 buf_k, buf_v, buf_len, *, group: int = 16
                                 ) -> jax.Array:
    """Oracle for
    :func:`repro.kernels.ct_paged_attention.ct_paged_attention_fused`:
    per-layer batched pool attention flash-merged with the fp TBQ buffer.

    qh [L, R, H, GQ, D]; planes [L, NP, H, BS, ...]; slot_state/slot_bits
    [L, R, NB, BS]; block_table [R, L, NB] RAW (-1 accepted);
    buf_k/buf_v [L, R, H, G, D]; buf_len [R].  Returns [L, R, H, GQ, D].
    """
    def one_layer(qh_l, kc, vc, ks, vs, state_l, bits_l, table_l, bk_l,
                  bv_l):
        out_p, m_p, l_p = ct_paged_attention_batched_ref(
            qh_l, kc, vc, ks, vs, state_l, bits_l, table_l, group=group)
        out_b, m_b, l_b = buffer_attention_batched_ref(
            qh_l, jnp.swapaxes(bk_l, 1, 2), jnp.swapaxes(bv_l, 1, 2),
            buf_len)
        return jax.vmap(merge_flash_ref)(out_p, m_p, l_p, out_b, m_b, l_b)

    return jax.vmap(one_layer, in_axes=(0, 0, 0, 0, 0, 0, 0, 1, 0, 0))(
        qh, k_codes, v_codes, k_scales, v_scales, slot_state, slot_bits,
        block_table, buf_k, buf_v)


def merge_flash_ref(out_a, m_a, l_a, out_b, m_b, l_b):
    """Merge two flash partitions (paged pool vs B_buf) — oracle for the
    wrapper's merge in ``ops.py``."""
    m = jnp.maximum(m_a, m_b)
    ca, cb = jnp.exp(m_a - m), jnp.exp(m_b - m)
    l = l_a * ca + l_b * cb
    h, gq, _ = m.shape
    sa = (l_a * ca / jnp.maximum(l, 1e-30))
    sb = (l_b * cb / jnp.maximum(l, 1e-30))
    oa = out_a.reshape(h, gq, -1) * sa
    ob = out_b.reshape(h, gq, -1) * sb
    return (oa + ob).reshape(out_a.shape)


def group_quant_ref(x: jax.Array, bits: int, group: int = 16):
    """Oracle for :func:`repro.kernels.group_quant.group_quant`."""
    return Q.quantize_group(x, bits, group)


def mamba_scan_ref(x, dt, b, c, a) -> jax.Array:
    """Oracle for :func:`repro.kernels.mamba_scan.mamba_scan`.

    x, dt [S, di]; b, c [S, N]; a [di, N].  Sequential jnp scan.
    """
    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        da = jnp.exp(dt_t[:, None] * a)
        h = da * h + (dt_t * x_t)[:, None] * b_t[None, :]
        return h, jnp.sum(h * c_t[None, :], axis=1)

    di, n = a.shape
    h0 = jnp.zeros((di, n), jnp.float32)
    _, ys = jax.lax.scan(step, h0, (x.astype(jnp.float32),
                                    dt.astype(jnp.float32),
                                    b.astype(jnp.float32),
                                    c.astype(jnp.float32)))
    return ys


def flash_prefill_ref(q, k, v, *, causal: bool = True,
                      window: int = 0) -> jax.Array:
    """Oracle for :func:`repro.kernels.flash_prefill.flash_prefill`.

    q: [S, Hq, D], k/v: [S, H, D].  GQA broadcast; optional sliding window.
    Returns [S, Hq, D] f32.
    """
    out, _, _ = flash_prefill_stats_ref(q, k, v, causal=causal,
                                        window=window)
    return out


def flash_prefill_stats_ref(q, k, v, *, causal: bool = True, window: int = 0,
                            kv_valid=None
                            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Like :func:`flash_prefill_ref` but also returns per-query flash stats
    (m, l) [S, Hq, 1] so the chunked-prefill path can merge this partition
    with the paged-pool partition.  ``kv_valid`` optionally masks padded kv
    positions ([T] bool)."""
    s_len, hq, d = q.shape
    t_len, h, _ = k.shape
    gq = hq // h
    qh = q.reshape(s_len, h, gq, d).astype(jnp.float32)
    scores = jnp.einsum("shgd,thd->hgst", qh, k.astype(jnp.float32))
    scores = scores / jnp.sqrt(float(d))
    i = jnp.arange(s_len)[:, None]
    j = jnp.arange(t_len)[None, :]
    mask = jnp.ones((s_len, t_len), bool)
    if causal:
        mask &= j <= i + (t_len - s_len)
    if window > 0:
        mask &= j > i + (t_len - s_len) - window
    if kv_valid is not None:
        mask &= kv_valid[None, :]
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)            # [h,g,s,1]
    p = jnp.exp(scores - m)
    p = jnp.where(mask[None, None], p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("hgst,thd->shgd", p / jnp.maximum(l, 1e-30),
                     v.astype(jnp.float32))
    # [h,g,s,1] -> [s, hq, 1]
    to_q = lambda a: a[..., 0].transpose(2, 0, 1).reshape(s_len, hq, 1)
    return out.reshape(s_len, hq, d), to_q(m), to_q(l)
