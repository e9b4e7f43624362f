"""Decoder-only transformer LM (dense / MoE / VLM-backbone).

Layer params are stacked on a leading [L] axis and the forward is a
``lax.scan`` over layers — HLO size is O(1) in depth (MaxText-style), which
keeps 88-layer lowering tractable and gives remat a natural boundary.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ArchFamily, ModelConfig
from repro.layers import attention as A
from repro.layers import embedding as E
from repro.layers import moe as MOE
from repro.layers.common import softcap, split_keys
from repro.layers.mlp import mlp, mlp_params
from repro.layers.norms import rmsnorm, rmsnorm_params


def _layer_params(key, cfg: ModelConfig, dtype) -> dict:
    k1, k2 = jax.random.split(key)
    p = {
        "attn": A.attn_params(k1, cfg, dtype),
        "norm1": rmsnorm_params(cfg.d_model),
        "norm2": rmsnorm_params(cfg.d_model),
    }
    if cfg.moe is not None:
        p["moe"] = MOE.moe_params(k2, cfg, dtype)
    else:
        p["mlp"] = mlp_params(k2, cfg.d_model, cfg.d_ff, cfg.mlp_gated, dtype)
    return p


def init(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    ke, kl, kf = split_keys(key, 3)
    layer_keys = jax.random.split(kl, cfg.num_layers)
    layers = jax.vmap(lambda k: _layer_params(k, cfg, dtype))(layer_keys)
    params = {
        "embed": E.embed_params(ke, cfg, dtype),
        "layers": layers,
        "final_norm": rmsnorm_params(cfg.d_model),
    }
    if cfg.family == ArchFamily.VLM:
        params["frontend"] = E.frontend_stub_params(kf, cfg, dtype)
    return params


def _block(cfg: ModelConfig, lp: dict, h: jax.Array, positions: jax.Array,
           causal: bool = True, kind: Optional[str] = None
           ) -> Tuple[jax.Array, jax.Array]:
    """One decoder block over [B,S,D].  Returns (h, moe_aux).  ``kind``
    is the layer's attention kind in a config with ``layer_kinds``."""
    from repro.distributed.sharding import constrain
    h = constrain(h, "dp", None, None)   # keep batch sharded through the scan
    a = A.attn_forward(lp["attn"], rmsnorm(lp["norm1"], h, cfg.norm_eps),
                       cfg, positions, causal=causal, kind=kind)
    h = h + a
    x2 = rmsnorm(lp["norm2"], h, cfg.norm_eps)
    if cfg.moe is not None and cfg.moe.num_held != cfg.moe.num_experts:
        # a share of the experts: the dropless held-expert layer
        b, s, d = x2.shape
        m = MOE.moe_held(lp["moe"], x2.reshape(b * s, d), cfg)[0]
        m, aux = m.reshape(b, s, d), jnp.float32(0)
    elif cfg.moe is not None:
        m, aux = MOE.moe_apply(lp["moe"], x2, cfg)
    else:
        m, aux = mlp(lp["mlp"], x2, cfg.act, cfg.mlp_gated), jnp.float32(0)
    return h + m, aux


def backbone(params: dict, h: jax.Array, cfg: ModelConfig,
             positions: jax.Array, *, remat: bool = False,
             causal: bool = True) -> Tuple[jax.Array, jax.Array]:
    """Scan the stacked layers over hidden states [B,S,D]; a config with
    ``layer_kinds`` runs its layers one by one, each with its kind."""

    def body(carry, lp, kind=None):
        h, aux = carry
        h, a = _block(cfg, lp, h, positions, causal, kind)
        return (h, aux + a), None

    if cfg.layer_kinds:
        carry = (h, jnp.float32(0))
        for i, kind in enumerate(cfg.layer_kinds):
            step = functools.partial(body, kind=kind)
            if remat:
                step = jax.checkpoint(step, prevent_cse=False)
            lp = jax.tree.map(lambda x: x[i], params["layers"])
            carry, _ = step(carry, lp)
        h, aux = carry
    else:
        if remat:
            body = jax.checkpoint(body, prevent_cse=False)
        (h, aux), _ = jax.lax.scan(body, (h, jnp.float32(0)),
                                   params["layers"])
    return rmsnorm(params["final_norm"], h, cfg.norm_eps), aux


def assemble_inputs(params: dict, batch: Dict[str, jax.Array],
                    cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """Embed tokens; VLM prepends projected stub patch embeddings."""
    h = E.embed(params["embed"], batch["tokens"], cfg)
    if cfg.family == ArchFamily.VLM and "patches" in batch:
        img = E.frontend_stub(params["frontend"],
                              batch["patches"].astype(h.dtype))
        h = jnp.concatenate([img, h], axis=1)
    positions = jnp.arange(h.shape[1])[None, :]
    return h, positions


def logits_fn(params: dict, batch: Dict[str, jax.Array], cfg: ModelConfig,
              *, remat: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Teacher-forced logits [B, S(+img), V] and MoE aux loss."""
    h, positions = assemble_inputs(params, batch, cfg)
    h, aux = backbone(params, h, cfg, positions, remat=remat)
    lg = E.unembed(params["embed"], h, cfg)
    return softcap(lg, cfg.logit_softcap), aux


def unembed_weight(params: dict, cfg: ModelConfig) -> jax.Array:
    return (params["embed"]["embedding"].T if cfg.tie_embeddings
            else params["embed"]["lm_head"])


def loss_fn(params: dict, batch: Dict[str, jax.Array], cfg: ModelConfig,
            *, remat: bool = False) -> Tuple[jax.Array, dict]:
    from repro.models.losses import chunked_softmax_xent
    h, positions = assemble_inputs(params, batch, cfg)
    h, aux = backbone(params, h, cfg, positions, remat=remat)
    targets = batch["targets"]
    if cfg.family == ArchFamily.VLM and "patches" in batch:
        h = h[:, -targets.shape[1]:]            # image positions carry no loss
    mask = batch.get("loss_mask", jnp.ones_like(targets, jnp.float32))
    loss = chunked_softmax_xent(h, unembed_weight(params, cfg), targets,
                                mask, cfg.logit_softcap)
    aux_w = cfg.moe.aux_loss_weight if cfg.moe is not None else 0.0
    total = loss + aux_w * aux / max(cfg.num_layers, 1)
    return total, {"nll": loss, "moe_aux": aux}


# ---------------------------------------------------------------------------
# FullKV serving paths (baseline; the ThinKV path lives in serving/engine.py)
# ---------------------------------------------------------------------------

def _uniform_layers_only(cfg: ModelConfig) -> None:
    if cfg.layer_kinds:
        raise NotImplementedError(
            "the FullKV baseline paths serve one attention kind; a config "
            "with layer_kinds is served by serving.engine.ThinKVEngine")


def prefill(params: dict, batch: Dict[str, jax.Array], cfg: ModelConfig):
    """Returns (logits_last [B,V], k_cache, v_cache [L,B,S,Hkv,hd])."""
    _uniform_layers_only(cfg)
    h, positions = assemble_inputs(params, batch, cfg)

    def body(h, lp):
        x1 = rmsnorm(lp["norm1"], h, cfg.norm_eps)
        a, k, v = A.attn_prefill_with_cache(lp["attn"], x1, cfg, positions)
        h = h + a
        x2 = rmsnorm(lp["norm2"], h, cfg.norm_eps)
        if cfg.moe is not None:
            m, _ = MOE.moe_apply(lp["moe"], x2, cfg)
        else:
            m = mlp(lp["mlp"], x2, cfg.act, cfg.mlp_gated)
        return h + m, (k, v)

    h, (kc, vc) = jax.lax.scan(body, h, params["layers"])
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    lg = softcap(E.unembed(params["embed"], h[:, -1], cfg), cfg.logit_softcap)
    return lg, kc, vc


def decode_step_fullkv(params: dict, token: jax.Array, pos: jax.Array,
                       k_cache: jax.Array, v_cache: jax.Array,
                       cache_len: jax.Array, cfg: ModelConfig):
    """Single-request FullKV decode step.

    token []; k_cache/v_cache [L,T,Hkv,hd]; returns (logits [V], caches).
    """
    _uniform_layers_only(cfg)
    h = E.embed(params["embed"], token[None], cfg)[0]

    def body(carry, inp):
        h = carry
        lp, kc_l, vc_l = inp
        x1 = rmsnorm(lp["norm1"], h, cfg.norm_eps)
        q, k, v = A.qkv_decode(lp["attn"], x1, cfg, pos)
        kc_l = jax.lax.dynamic_update_index_in_dim(kc_l, k, cache_len, 0)
        vc_l = jax.lax.dynamic_update_index_in_dim(vc_l, v, cache_len, 0)
        o = A.decode_attend_fullkv(q, kc_l, vc_l, cache_len + 1,
                                   window=cfg.sliding_window)
        h = h + A.out_proj(lp["attn"], o)
        x2 = rmsnorm(lp["norm2"], h, cfg.norm_eps)
        if cfg.moe is not None:
            m, _ = MOE.moe_apply(lp["moe"], x2[None, None], cfg)
            m = m[0, 0]
        else:
            m = mlp(lp["mlp"], x2, cfg.act, cfg.mlp_gated)
        return h + m, (kc_l, vc_l)

    h, (kc, vc) = jax.lax.scan(body, h, (params["layers"], k_cache, v_cache))
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    lg = softcap(E.unembed(params["embed"], h, cfg), cfg.logit_softcap)
    return lg, kc, vc