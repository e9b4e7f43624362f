"""Jitted serving steps for every architecture family.

Three step kinds per the assignment's shape semantics:
* ``prefill_step``  — full forward over the prompt, last-token logits;
* ``decode_step``   — ONE new token against existing state (FullKV cache of
  ``seq_len``, or the ThinKV budget-bound CT pool);
* the ThinKV commit/refresh control steps are separate jits (they run every
  g / tau tokens; the paper's Table 5 call rates justify splitting them out
  of the common path).

All steps are functions of (params, batch-pytree) so the multi-pod dry-run
can lower them against ShapeDtypeStructs with explicit shardings.

The decode attention here is the XLA (reference) path, which materializes
the dequantized pool — correct everywhere, and what the dry-run costs.  On
real TPUs the Pallas ``ct_paged_attention`` kernel replaces it (fused
dequant; see EXPERIMENTS.md §Perf for the analytic delta).
"""
from __future__ import annotations

import functools
import os
from typing import Callable, Dict

import jax
import jax.numpy as jnp

from repro.config import ArchFamily, ModelConfig, ThinKVConfig
from repro.core import quantization as Q
from repro.layers import attention as A
from repro.layers import embedding as E
from repro.layers import ssm as S
from repro.layers.common import softcap
from repro.layers.mlp import mlp
from repro.layers.moe import moe_apply
from repro.layers.norms import layernorm, rmsnorm
from repro.models import encdec, hybrid, lm, ssm_lm

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------

def make_prefill_step(model, cfg: ModelConfig) -> Callable:
    """(params, batch) -> last-token logits [B, V]."""

    if cfg.family in (ArchFamily.DENSE, ArchFamily.MOE, ArchFamily.VLM):
        def step(params, batch):
            h, positions = lm.assemble_inputs(params, batch, cfg)
            h, _ = lm.backbone(params, h, cfg, positions, remat=True)
            lg = E.unembed(params["embed"], h[:, -1], cfg)
            return softcap(lg, cfg.logit_softcap)
        return step

    if cfg.family == ArchFamily.ENCDEC:
        def step(params, batch):
            h = encdec.hidden_fn(params, batch, cfg, remat=True)
            return E.unembed(params["embed"], h[:, -1], cfg)
        return step

    if cfg.family == ArchFamily.SSM:
        def step(params, batch):
            h = ssm_lm.hidden_fn(params, batch, cfg, remat=True)
            return E.unembed(params["embed"], h[:, -1], cfg)
        return step

    def step(params, batch):          # hybrid
        h = hybrid.hidden_fn(params, batch, cfg, remat=True)
        return E.unembed(params["embed"], h[:, -1], cfg)
    return step


# ---------------------------------------------------------------------------
# FullKV decode (baseline)
# ---------------------------------------------------------------------------

def make_decode_step_fullkv(cfg: ModelConfig) -> Callable:
    """(params, batch) -> (logits [B,V], new k/v caches).

    batch: tokens [B], positions [B], k_cache/v_cache [B,L,T,H,hd],
    cache_len [B] (+ family-specific state).
    """
    if cfg.family in (ArchFamily.DENSE, ArchFamily.MOE, ArchFamily.VLM):
        def one(params, token, pos, kc, vc, clen):
            return lm.decode_step_fullkv(params, token, pos, kc, vc, clen,
                                         cfg)

        def step(params, batch):
            return jax.vmap(one, in_axes=(None, 0, 0, 0, 0, 0))(
                params, batch["tokens"], batch["positions"],
                batch["k_cache"], batch["v_cache"], batch["cache_len"])
        return step

    if cfg.family == ArchFamily.ENCDEC:
        def one(params, token, pos, kc, vc, clen, ck, cv):
            return encdec.decode_step_fullkv(params, token, pos, kc, vc,
                                             clen, ck, cv, cfg)

        def step(params, batch):
            return jax.vmap(one, in_axes=(None,) + (0,) * 7)(
                params, batch["tokens"], batch["positions"],
                batch["k_cache"], batch["v_cache"], batch["cache_len"],
                batch["cross_k"], batch["cross_v"])
        return step

    if cfg.family == ArchFamily.SSM:
        def one(params, token, conv, h):
            lg, new = ssm_lm.decode_step(params, token,
                                         S.Mamba1State(conv=conv, h=h), cfg)
            return lg, new.conv, new.h

        def step(params, batch):
            return jax.vmap(one, in_axes=(None, 0, 0, 0))(
                params, batch["tokens"], batch["conv_state"],
                batch["ssm_state"])
        return step

    # hybrid
    def one(params, token, pos, conv, h, kc, vc, clen):
        st = S.Mamba2State(conv=conv, h=h)
        lg, new, kc2, vc2 = hybrid.decode_step_fullkv(
            params, token, pos, st, kc, vc, clen, cfg)
        return lg, new.conv, new.h, kc2, vc2

    def step(params, batch):
        return jax.vmap(one, in_axes=(None,) + (0,) * 7)(
            params, batch["tokens"], batch["positions"],
            batch["conv_state"], batch["ssm_state"], batch["k_cache"],
            batch["v_cache"], batch["cache_len"])
    return step


# ---------------------------------------------------------------------------
# ThinKV decode (the paper's serve path)
# ---------------------------------------------------------------------------

def _flash_part(q, k, v, valid):
    """Flash-stats attention over one partition: returns (out, m, l).

    Operands stay in their storage dtype (bf16 on the optimized path);
    scores/stats accumulate in f32 via preferred_element_type (§Perf iter 3
    — halves the dequantized-pool HBM traffic)."""
    hq, hd = q.shape
    hkv = k.shape[1]
    gq = hq // hkv
    qh = q.reshape(hkv, gq, hd)
    s = jnp.einsum("hgd,nhd->hgn", qh, k,
                   preferred_element_type=jnp.float32) / jnp.sqrt(float(hd))
    s = jnp.where(valid[None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = jnp.where(valid[None, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("hgn,nhd->hgd",
                     (p / jnp.maximum(l, 1e-30)).astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out, m, l


def _merge_parts(a, b, hq, hd):
    (oa, ma, la), (ob, mb, lb) = a, b
    m = jnp.maximum(ma, mb)
    ca, cb = jnp.exp(ma - m), jnp.exp(mb - m)
    l = jnp.maximum(la * ca + lb * cb, 1e-30)
    out = (oa * (la * ca / l) + ob * (lb * cb / l))
    return out.reshape(hq, hd)


def _pool_attention(q, k_codes, v_codes, k_scales, v_scales, slot_state,
                    slot_bits, buf_k, buf_v, buf_len):
    """One layer's decode attention over (quantized pool ∪ fp buffer).

    q [Hq,hd]; pool planes PAGED [NB,BS,H,hd] (flattened here); buffer
    [G,H,hd].  XLA reference path: densely dequantizes the pool.

    §Perf iteration: the pool (NS sharded over `model`) and the buffer
    (replicated, 16 tokens) are attended SEPARATELY and merged via flash
    stats — concatenating them forced GSPMD into involuntary full
    rematerialization of the mixed-sharding operand.
    """
    nb, bs = k_codes.shape[0], k_codes.shape[1]
    flat = lambda a: a.reshape(nb * bs, *a.shape[2:])
    k_codes, v_codes = flat(k_codes), flat(v_codes)
    k_scales, v_scales = flat(k_scales), flat(v_scales)
    bits = slot_bits.astype(jnp.int32)[:, None, None]
    deq_dtype = jnp.float32 if os.environ.get("REPRO_F32_DEQUANT") \
        else jnp.bfloat16
    kd = Q.dequantize_by_bitcode(k_codes, k_scales.astype(jnp.float32),
                                 bits).astype(deq_dtype)
    vd = Q.dequantize_by_bitcode(v_codes, v_scales.astype(jnp.float32),
                                 bits).astype(deq_dtype)
    g = buf_k.shape[0]
    hq, hd = q.shape
    if os.environ.get("REPRO_CONCAT_BUF"):
        # pre-optimization path kept for baseline measurement: concatenating
        # the model-sharded pool with the replicated buffer forces GSPMD
        # involuntary rematerialization
        k = jnp.concatenate([kd.astype(jnp.float32),
                             buf_k.astype(jnp.float32)], 0)
        v = jnp.concatenate([vd.astype(jnp.float32),
                             buf_v.astype(jnp.float32)], 0)
        valid = jnp.concatenate([slot_state == 1, jnp.arange(g) < buf_len],
                                0)
        out, _, _ = _flash_part(q.astype(jnp.float32), k, v, valid)
        return out.reshape(hq, hd).astype(q.dtype)
    part_p = _flash_part(q.astype(deq_dtype), kd, vd, slot_state == 1)
    part_b = _flash_part(q.astype(deq_dtype), buf_k.astype(deq_dtype),
                         buf_v.astype(deq_dtype), jnp.arange(g) < buf_len)
    return _merge_parts(part_p, part_b, hq, hd).astype(q.dtype)


def _pool_attention_kernel(q, k_codes, v_codes, k_scales, v_scales,
                           slot_state, slot_bits, buf_k, buf_v, buf_len,
                           force):
    """Kernel-dispatch variant of :func:`_pool_attention`: one
    ``ops.paged_decode_attention_fused`` launch (L=1, R=1) reads the pool
    through an identity table (serve_step batches are per-request pools by
    construction) AND folds the fp-buffer attention into the kernel's final
    grid step — the (pool, buffer) flash merge happens in VMEM, no (m, l)
    stats plumbing back to XLA.  The batch's token-major ``[NB, BS, H,
    ...]`` pages are transposed to the kernel's ``[NB, H, BS, ...]``."""
    from repro.kernels import ops as K
    nb, bs, h = k_codes.shape[0], k_codes.shape[1], k_codes.shape[2]
    hq, hd = q.shape
    gq = hq // h
    qh = q.reshape(1, 1, h, gq, hd).astype(jnp.float32)
    table = jnp.arange(nb, dtype=jnp.int32)[None, None]       # [R=1, L=1]
    pages = lambda a: jnp.swapaxes(a, 1, 2)[None]
    heads = lambda b: jnp.swapaxes(b, 0, 1)[None, None]
    out = K.paged_decode_attention_fused(
        qh, pages(k_codes), pages(v_codes), pages(k_scales), pages(v_scales),
        slot_state.reshape(1, 1, nb, bs), slot_bits.reshape(1, 1, nb, bs),
        table, heads(buf_k), heads(buf_v),
        buf_len.reshape(1).astype(jnp.int32), force=force)
    return out.reshape(hq, hd).astype(q.dtype)


def make_decode_step_thinkv(cfg: ModelConfig, tk: ThinKVConfig, *,
                            backend: str = "reference",
                            force: str | None = None) -> Callable:
    """(params, batch) -> (logits [B,V], buf_k, buf_v, buf_len).

    batch carries the CT pool planes in PAGED layout
    ([B, L_attn, NB, BS, ...]) and the TBQ buffer; the common decode path
    only *reads* the pool and appends the new token's KV to the buffer
    (commit/refresh are separate steps).

    ``backend="reference"`` densely dequantizes the pool (XLA; what the
    dry-run costs); ``backend="kernel"`` routes the pool read through
    ``ct_paged_attention`` (compiled on TPU, oracle/interpret elsewhere
    per ``force``).
    """
    n_attn = cfg.num_attention_layers()
    assert backend in ("reference", "kernel"), backend
    if backend == "kernel":
        pool_attn = functools.partial(_pool_attention_kernel, force=force)
    else:
        pool_attn = _pool_attention

    if cfg.family in (ArchFamily.DENSE, ArchFamily.MOE, ArchFamily.VLM):
        def one(params, token, pos, kcod, vcod, ksc, vsc, sst, sbt,
                buf_k, buf_v, buf_len):
            h = E.embed(params["embed"], token[None], cfg)[0]

            def body(h, inp):
                (lp, kcod_l, vcod_l, ksc_l, vsc_l, sst_l, sbt_l, bk_l,
                 bv_l) = inp
                x1 = rmsnorm(lp["norm1"], h, cfg.norm_eps)
                q, k, v = A.qkv_decode(lp["attn"], x1, cfg, pos)
                bk_l = jax.lax.dynamic_update_index_in_dim(bk_l,
                                                           k.astype(bk_l.dtype),
                                                           buf_len, 0)
                bv_l = jax.lax.dynamic_update_index_in_dim(bv_l,
                                                           v.astype(bv_l.dtype),
                                                           buf_len, 0)
                o = pool_attn(q, kcod_l, vcod_l, ksc_l, vsc_l, sst_l,
                                    sbt_l, bk_l, bv_l, buf_len + 1)
                h = h + A.out_proj(lp["attn"], o)
                x2 = rmsnorm(lp["norm2"], h, cfg.norm_eps)
                if cfg.moe is not None:
                    m, _ = moe_apply(lp["moe"], x2[None, None], cfg)
                    m = m[0, 0]
                else:
                    m = mlp(lp["mlp"], x2, cfg.act, cfg.mlp_gated)
                return h + m, (bk_l, bv_l)

            h, (bk, bv) = jax.lax.scan(
                body, h, (params["layers"], kcod, vcod, ksc, vsc, sst, sbt,
                          buf_k, buf_v))
            h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
            lg = softcap(E.unembed(params["embed"], h, cfg),
                         cfg.logit_softcap)
            return lg, bk, bv

        def step(params, batch):
            lg, bk, bv = jax.vmap(one, in_axes=(None,) + (0,) * 11)(
                params, batch["tokens"], batch["positions"],
                batch["k_codes"], batch["v_codes"], batch["k_scales"],
                batch["v_scales"], batch["slot_state"], batch["slot_bits"],
                batch["buf_k"], batch["buf_v"], batch["buf_len"])
            return lg, bk, bv, batch["buf_len"] + 1
        return step

    if cfg.family == ArchFamily.ENCDEC:
        def one(params, token, pos, kcod, vcod, ksc, vsc, sst, sbt,
                buf_k, buf_v, buf_len, ckc, cvc, cks, cvs):
            h = E.embed(params["embed"], token[None], cfg)[0]
            h = h + jax.lax.dynamic_index_in_dim(
                params["dec_pos"], pos, 0, keepdims=False).astype(h.dtype)

            def body(h, inp):
                (lp, kcod_l, vcod_l, ksc_l, vsc_l, sst_l, sbt_l, bk_l, bv_l,
                 ckc_l, cvc_l, cks_l, cvs_l) = inp
                x1 = layernorm(lp["norm1"], h)
                q, k, v = A.qkv_decode(lp["self_attn"], x1, cfg, pos)
                bk_l = jax.lax.dynamic_update_index_in_dim(
                    bk_l, k.astype(bk_l.dtype), buf_len, 0)
                bv_l = jax.lax.dynamic_update_index_in_dim(
                    bv_l, v.astype(bv_l.dtype), buf_len, 0)
                o = pool_attn(q, kcod_l, vcod_l, ksc_l, vsc_l, sst_l,
                                    sbt_l, bk_l, bv_l, buf_len + 1)
                h = h + A.out_proj(lp["self_attn"], o)
                x2 = layernorm(lp["norm2"], h)
                qc, _, _ = A.qkv_decode(lp["cross_attn"], x2, cfg, pos)
                # TBQ'd cross KV (NVFP4, never evicted): dequant to bf16
                ck_l = Q.dequantize_group(ckc_l, cks_l.astype(jnp.float32),
                                          4).astype(jnp.bfloat16)
                cv_l = Q.dequantize_group(cvc_l, cvs_l.astype(jnp.float32),
                                          4).astype(jnp.bfloat16)
                oc = A.decode_attend_fullkv(qc, ck_l, cv_l,
                                            jnp.int32(ck_l.shape[0]))
                h = h + A.out_proj(lp["cross_attn"], oc)
                h = h + mlp(lp["mlp"], layernorm(lp["norm3"], h), "gelu",
                            False)
                return h, (bk_l, bv_l)

            h, (bk, bv) = jax.lax.scan(
                body, h, (params["decoder"], kcod, vcod, ksc, vsc, sst, sbt,
                          buf_k, buf_v, ckc, cvc, cks, cvs))
            h = layernorm(params["final_norm"], h)
            return E.unembed(params["embed"], h, cfg), bk, bv

        def step(params, batch):
            lg, bk, bv = jax.vmap(one, in_axes=(None,) + (0,) * 15)(
                params, batch["tokens"], batch["positions"],
                batch["k_codes"], batch["v_codes"], batch["k_scales"],
                batch["v_scales"], batch["slot_state"], batch["slot_bits"],
                batch["buf_k"], batch["buf_v"], batch["buf_len"],
                batch["cross_k_codes"], batch["cross_v_codes"],
                batch["cross_k_scales"], batch["cross_v_scales"])
            return lg, bk, bv, batch["buf_len"] + 1
        return step

    if cfg.family == ArchFamily.SSM:
        # attention-free: ThinKV inapplicable; identical to FullKV path
        return make_decode_step_fullkv(cfg)

    # ---- hybrid: mamba2 backbone + ThinKV on shared-attn invocations ----
    def one(params, token, pos, conv, hstate, kcod, vcod, ksc, vsc, sst,
            sbt, buf_k, buf_v, buf_len):
        h = E.embed(params["embed"], token[None], cfg)[0]
        ng = cfg.num_layers // cfg.hybrid_attn_every
        e = cfg.hybrid_attn_every
        tail = cfg.num_layers - ng * e
        sp = params["shared"]
        st = S.Mamba2State(conv=conv, h=hstate)

        def mamba_body(h, inp):
            lp, st_l = inp
            y, st2 = S.mamba2_decode_step(
                lp["mixer"], rmsnorm(lp["norm"], h, cfg.norm_eps), st_l, cfg)
            return h + y, st2

        grouped = jax.tree.map(
            lambda x: x[: ng * e].reshape(ng, e, *x.shape[1:]),
            params["layers"])
        tail_p = jax.tree.map(lambda x: x[ng * e:], params["layers"])
        gstate = jax.tree.map(
            lambda x: x[: ng * e].reshape(ng, e, *x.shape[1:]), st)
        tstate = jax.tree.map(lambda x: x[ng * e:], st)

        def group_body(h, inp):
            gp, gst, kcod_l, vcod_l, ksc_l, vsc_l, sst_l, sbt_l, bk_l, bv_l \
                = inp
            h, gst2 = jax.lax.scan(mamba_body, h, (gp, gst))
            x1 = rmsnorm(sp["norm1"], h, cfg.norm_eps)
            q, k, v = A.qkv_decode(sp["attn"], x1, cfg, pos)
            bk_l = jax.lax.dynamic_update_index_in_dim(
                bk_l, k.astype(bk_l.dtype), buf_len, 0)
            bv_l = jax.lax.dynamic_update_index_in_dim(
                bv_l, v.astype(bv_l.dtype), buf_len, 0)
            o = pool_attn(q, kcod_l, vcod_l, ksc_l, vsc_l, sst_l,
                                sbt_l, bk_l, bv_l, buf_len + 1)
            h = h + A.out_proj(sp["attn"], o)
            h = h + mlp(sp["mlp"], rmsnorm(sp["norm2"], h, cfg.norm_eps),
                        cfg.act, cfg.mlp_gated)
            return h, (gst2, bk_l, bv_l)

        h, (gstate2, bk, bv) = jax.lax.scan(
            group_body, h, (grouped, gstate, kcod, vcod, ksc, vsc, sst, sbt,
                            buf_k, buf_v))
        if tail:
            h, tstate2 = jax.lax.scan(mamba_body, h, (tail_p, tstate))
        else:
            tstate2 = tstate
        new_state = jax.tree.map(
            lambda g_, t_: jnp.concatenate(
                [g_.reshape(ng * e, *g_.shape[2:]), t_], 0), gstate2, tstate2)
        h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
        lg = E.unembed(params["embed"], h, cfg)
        return lg, new_state.conv, new_state.h, bk, bv

    def step(params, batch):
        lg, conv, hs, bk, bv = jax.vmap(one, in_axes=(None,) + (0,) * 13)(
            params, batch["tokens"], batch["positions"],
            batch["conv_state"], batch["ssm_state"], batch["k_codes"],
            batch["v_codes"], batch["k_scales"], batch["v_scales"],
            batch["slot_state"], batch["slot_bits"], batch["buf_k"],
            batch["buf_v"], batch["buf_len"])
        return lg, conv, hs, bk, bv, batch["buf_len"] + 1
    return step
