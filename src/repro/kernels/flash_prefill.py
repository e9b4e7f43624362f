"""Blocked causal flash-attention Pallas kernel for the prefill phase.

The paper uses FlashAttention-2 for all prefill/baseline paths (Sec. 6.1);
this is the TPU-native equivalent: (q-block x kv-block) grid with running
softmax in VMEM scratch, optional sliding window (mixtral), GQA via a
q-head grid axis.

Grid: (heads_q, q_blocks, kv_blocks); kv fastest so the (m, l, acc) scratch
carries across kv steps for a fixed q block.  Causality skips kv blocks
strictly above the diagonal via masking (blocks fully above contribute 0).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            block_q: int, block_k: int, kv_blocks: int, causal: bool,
            window: int, scale: float, mo_ref=None, lo_ref=None):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, m_ref.dtype)
        l_ref[...] = jnp.zeros(l_ref.shape, l_ref.dtype)

    q = q_ref[0].astype(jnp.float32)                     # [bq, D]
    k = k_ref[0].astype(jnp.float32)                     # [bk, D]
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
    cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
    mask = jnp.ones_like(s, dtype=bool)
    if causal:
        mask &= cols <= rows
    if window > 0:
        mask &= cols > rows - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == kv_blocks - 1)
    def _final():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)
        if mo_ref is not None:
            mo_ref[0] = m_ref[...]
            lo_ref[0] = l_ref[...]


def _kernel_stats(q_ref, k_ref, v_ref, o_ref, mo_ref, lo_ref, m_ref, l_ref,
                  acc_ref, **kw):
    """Stats variant: (m, l) are also OUTPUTS (written at the last kv step)
    so the chunked-prefill path can flash-merge with the paged pool."""
    _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            mo_ref=mo_ref, lo_ref=lo_ref, **kw)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret",
                                             "return_stats"))
def flash_prefill(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True, window: int = 0, block_q: int = 128,
                  block_k: int = 128, interpret: bool = False,
                  return_stats: bool = False):
    """q [S, Hq, D], k/v [S, H, D] -> out [S, Hq, D] (f32).

    GQA: each q head attends the kv head ``h // (Hq//H)``.
    ``return_stats`` additionally returns per-query flash stats
    (m, l) [S, Hq, 1] for partition merging.
    """
    s_len, hq, d = q.shape
    _, h, _ = k.shape
    gq = hq // h
    bq = min(block_q, s_len)
    bk = min(block_k, s_len)
    assert s_len % bq == 0 and s_len % bk == 0, (s_len, bq, bk)
    qb, kb = s_len // bq, s_len // bk

    qt = jnp.swapaxes(q, 0, 1)                           # [Hq, S, D]
    kt = jnp.swapaxes(k, 0, 1)                           # [H, S, D]
    vt = jnp.swapaxes(v, 0, 1)

    grid = (hq, qb, kb)
    kw = dict(block_q=bq, block_k=bk, kv_blocks=kb, causal=causal,
              window=window, scale=1.0 / (d ** 0.5))
    in_specs = [
        pl.BlockSpec((1, bq, d), lambda hh, qi, ki: (hh, qi, 0)),
        pl.BlockSpec((1, bk, d), lambda hh, qi, ki: (hh // gq, ki, 0)),
        pl.BlockSpec((1, bk, d), lambda hh, qi, ki: (hh // gq, ki, 0)),
    ]
    o_spec = pl.BlockSpec((1, bq, d), lambda hh, qi, ki: (hh, qi, 0))
    scratch = [
        pltpu.VMEM((bq, 1), jnp.float32),
        pltpu.VMEM((bq, 1), jnp.float32),
        pltpu.VMEM((bq, d), jnp.float32),
    ]
    if return_stats:
        s_spec = pl.BlockSpec((1, bq, 1), lambda hh, qi, ki: (hh, qi, 0))
        out, m, l = pl.pallas_call(
            functools.partial(_kernel_stats, **kw),
            name="flash_prefill",
            grid=grid,
            in_specs=in_specs,
            out_specs=[o_spec, s_spec, s_spec],
            out_shape=[
                jax.ShapeDtypeStruct((hq, s_len, d), jnp.float32),
                jax.ShapeDtypeStruct((hq, s_len, 1), jnp.float32),
                jax.ShapeDtypeStruct((hq, s_len, 1), jnp.float32),
            ],
            scratch_shapes=scratch,
            interpret=interpret,
        )(qt, kt, vt)
        return (jnp.swapaxes(out, 0, 1), jnp.swapaxes(m, 0, 1),
                jnp.swapaxes(l, 0, 1))
    out = pl.pallas_call(
        functools.partial(_kernel, **kw),
        name="flash_prefill",
        grid=grid,
        in_specs=in_specs,
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((hq, s_len, d), jnp.float32),
        scratch_shapes=scratch,
        interpret=interpret,
    )(qt, kt, vt)
    return jnp.swapaxes(out, 0, 1)