"""GQA/MHA attention: projections + train/prefill/decode compute paths.

Conventions:
* train/prefill operate on batched sequences ``x [B, S, D]``;
* decode operates on a single request's token ``x [D]`` (engines vmap);
* keys are cached POST-RoPE (paper App. D.4), so cached attention needs no
  position information — this is what makes CT slot reuse permutation-safe.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import WINDOW, ModelConfig
from repro.layers.common import dense_init, split_keys
from repro.layers.rope import apply_rope, rope_freqs, rope_table

NEG_INF = -1e30


def attn_params(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    d, hd = cfg.d_model, cfg.head_dim
    ks = split_keys(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, cfg.num_heads * hd), dtype=dtype),
        "wk": dense_init(ks[1], (d, cfg.num_kv_heads * hd), dtype=dtype),
        "wv": dense_init(ks[2], (d, cfg.num_kv_heads * hd), dtype=dtype),
        "wo": dense_init(ks[3], (cfg.num_heads * hd, d), dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.num_heads * hd,), dtype)
        p["bk"] = jnp.zeros((cfg.num_kv_heads * hd,), dtype)
        p["bv"] = jnp.zeros((cfg.num_kv_heads * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
    return p


def _head_rms(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def _project_qkv(p: dict, x: jax.Array, cfg: ModelConfig):
    """x [..., D] -> q [..., Hq, hd], k/v [..., Hkv, hd] (pre-RoPE; with
    ``qk_norm``, q and k RMS-normed per head)."""
    hd = cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(*x.shape[:-1], cfg.num_heads, hd)
    k = k.reshape(*x.shape[:-1], cfg.num_kv_heads, hd)
    v = v.reshape(*x.shape[:-1], cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = _head_rms(q, p["q_norm"], cfg.norm_eps)
        k = _head_rms(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def qkv_rope(p: dict, x: jax.Array, cfg: ModelConfig, positions: jax.Array,
             kind: str):
    """Projections with the rope of layer kind ``kind``: x [T, D],
    positions [T] -> q [T, Hq, hd], k/v [T, Hkv, hd]."""
    q, k, v = _project_qkv(p, x, cfg)
    cos, sin = rope_table(positions, cfg.head_dim, cfg.rope_of(kind))
    return apply_rope(q, cos, sin), apply_rope(k, cos, sin), v


def qkv_decode(p: dict, x: jax.Array, cfg: ModelConfig,
               position: jax.Array):
    """Single-token projections with RoPE.  x [D] -> ([Hq,hd],[Hkv,hd],[Hkv,hd])."""
    q, k, v = _project_qkv(p, x[None, :], cfg)
    q, k, v = q[0], k[0], v[0]
    if cfg.position_embedding.value == "rope":
        cos, sin = rope_freqs(position, cfg.head_dim, cfg.rope_theta)
        q = apply_rope(q, cos[None, :], sin[None, :])
        k = apply_rope(k, cos[None, :], sin[None, :])
    return q, k, v


def out_proj(p: dict, attn: jax.Array) -> jax.Array:
    """attn [..., Hq, hd] -> [..., D]."""
    return attn.reshape(*attn.shape[:-2], -1) @ p["wo"]


def _dense_attention(q, k, v, *, causal: bool, window: int) -> jax.Array:
    """q [B,S,Hq,hd] x k/v [B,T,Hkv,hd] -> [B,S,Hq,hd].  GQA broadcast;
    materializes [S,T] scores — small-sequence path only."""
    b, s, hq, hd = q.shape
    _, t, hkv, _ = k.shape
    g = hq // hkv
    qh = q.reshape(b, s, hkv, g, hd)
    scores = jnp.einsum("bshgd,bthd->bhgst", qh.astype(jnp.float32),
                        k.astype(jnp.float32)) / jnp.sqrt(float(hd))
    i = jnp.arange(s)[:, None]
    j = jnp.arange(t)[None, :]
    mask = jnp.ones((s, t), bool)
    if causal:
        mask &= j <= i + (t - s)
    if window > 0:
        mask &= j > i + (t - s) - window
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgst,bthd->bshgd", probs, v.astype(jnp.float32))
    return out.reshape(b, s, hq, hd).astype(q.dtype)


# sequences longer than this use the chunked (flash-style) path
_CHUNK_THRESHOLD = 2048
_Q_CHUNK = 512


def _chunked_attention(q, k, v, *, causal: bool, window: int,
                       q_chunk: int = _Q_CHUNK) -> jax.Array:
    """Memory-bounded exact attention: scan over q chunks; per-chunk scores
    are [B,H,q_chunk,T].  The XLA analogue of FlashAttention used by the
    train/prefill paths at long sequence (the TPU runtime path is the
    Pallas ``flash_prefill`` kernel)."""
    b, s, hq, hd = q.shape
    _, t, hkv, _ = k.shape
    g = hq // hkv
    qc = q_chunk
    while s % qc != 0:
        qc //= 2
    nq = s // qc
    qh = q.reshape(b, nq, qc, hkv, g, hd)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    j = jnp.arange(t)

    import os
    # REPRO_BF16_SCORES opts into bf16 score/prob materialization.  Measured
    # on the CPU backend it is neutral-to-negative (XLA CPU upcasts bf16
    # elementwise math to f32 and adds conversions — §Perf llama4 iter 4,
    # refuted); on TPU the production answer is the Pallas flash kernel
    # (kernels/flash_prefill.py), which keeps scores in VMEM entirely.
    sdt = jnp.bfloat16 if os.environ.get("REPRO_BF16_SCORES") \
        else jnp.float32

    def body(_, inp):
        qi, qblk = inp
        scores = jnp.einsum("bshgd,bthd->bhgst", qblk.astype(sdt),
                            kf.astype(sdt),
                            preferred_element_type=jnp.float32)
        scores = scores / jnp.sqrt(float(hd))
        i = qi * qc + jnp.arange(qc)[:, None] + (t - s)
        mask = jnp.ones((qc, t), bool)
        if causal:
            mask &= j[None, :] <= i
        if window > 0:
            mask &= j[None, :] > i - window
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
        m = jnp.max(scores, axis=-1, keepdims=True)
        probs = jnp.exp(scores - m).astype(sdt)
        denom = jnp.sum(probs, axis=-1, keepdims=True).astype(jnp.float32)
        out = jnp.einsum("bhgst,bthd->bshgd", probs, vf.astype(sdt),
                         preferred_element_type=jnp.float32)
        # denom [b,h,g,s,1] -> [b,s,h,g,1] to divide out [b,s,h,g,d]
        dn = jnp.maximum(denom[..., 0], 1e-30).transpose(0, 3, 1, 2)
        out = out / dn[..., None]
        return None, out.reshape(b, qc, hq, hd).astype(q.dtype)

    body = jax.checkpoint(body, prevent_cse=False)
    _, outs = jax.lax.scan(
        body, None, (jnp.arange(nq), jnp.moveaxis(qh, 1, 0)))
    return jnp.moveaxis(outs, 0, 1).reshape(b, s, hq, hd)


def _full_attention(q, k, v, *, causal: bool, window: int,
                    cross_len: Optional[int] = None) -> jax.Array:
    if q.shape[1] > _CHUNK_THRESHOLD:
        return _chunked_attention(q, k, v, causal=causal, window=window)
    return _dense_attention(q, k, v, causal=causal, window=window)


def attn_forward(p: dict, x: jax.Array, cfg: ModelConfig,
                 positions: jax.Array, *, causal: bool = True,
                 kv_override: Optional[Tuple[jax.Array, jax.Array]] = None,
                 kind: Optional[str] = None) -> jax.Array:
    """Full-sequence attention for train/prefill.  x [B,S,D].

    ``kv_override`` supplies external (k, v) for cross-attention
    ([B,T,Hkv,hd], already position-encoded or encoder-side).  ``kind``
    (a layer of a config with ``layer_kinds``) selects that kind's rope
    and window.
    """
    q, k, v = _project_qkv(p, x, cfg)
    window = cfg.sliding_window
    if kind is not None:
        cos, sin = rope_table(positions, cfg.head_dim, cfg.rope_of(kind))
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        window = cfg.sliding_window if kind == WINDOW else 0
    elif cfg.position_embedding.value == "rope":
        cos, sin = rope_freqs(positions, cfg.head_dim, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    if kv_override is not None:
        k, v = kv_override
        causal = False
    import os
    if not os.environ.get("REPRO_NO_RING") and causal and \
            cfg.sliding_window == 0:
        # ADAPTIVE ring (context-parallel) attention over the `model` axis:
        # heads stay whole, sequence shards, K/V rotate via ppermute.
        # Selected exactly where GSPMD head-sharding breaks down (measured,
        # EXPERIMENTS.md §Perf ring iteration):
        #   - heads % |model| != 0 (qwen2 28, llama4 40, paligemma 8): GSPMD
        #     replicates activations -> up to 87x collective reduction;
        #   - d_model/|model| < 128 (whisper): over-sharded matmuls.
        # Divisible-head large models keep the head-sharded GSPMD path
        # (ring measured worse there: duplicated flash accumulators).
        # Active only under an installed production mesh (launchers);
        # single-device tests and CPU engines take the XLA path below.
        from repro.distributed.ring_attention import ring_attention
        from repro.distributed.sharding import _CONSTRAINT_MESH
        mesh = _CONSTRAINT_MESH[0]
        if mesh is not None and "model" in mesh.axis_names and \
                q.shape[1] % mesh.shape["model"] == 0:
            tp = mesh.shape["model"]
            if cfg.num_heads % tp != 0 or cfg.d_model // tp < 128:
                out = ring_attention(q, k, v, mesh)
                return out_proj(p, out)
    out = _full_attention(q, k, v, causal=causal, window=window)
    return out_proj(p, out)


def attn_prefill_with_cache(p: dict, x: jax.Array, cfg: ModelConfig,
                            positions: jax.Array):
    """Prefill returning (y [B,S,D], k_cache, v_cache [B,S,Hkv,hd] post-RoPE)."""
    q, k, v = _project_qkv(p, x, cfg)
    if cfg.position_embedding.value == "rope":
        cos, sin = rope_freqs(positions, cfg.head_dim, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    out = _full_attention(q, k, v, causal=True, window=cfg.sliding_window)
    return out_proj(p, out), k, v


def cross_kv(p: dict, enc: jax.Array, cfg: ModelConfig):
    """Encoder-side K/V for cross attention: enc [B,T,D] -> [B,T,Hkv,hd]."""
    hd = cfg.head_dim
    k = (enc @ p["wk"]).reshape(*enc.shape[:-1], cfg.num_kv_heads, hd)
    v = (enc @ p["wv"]).reshape(*enc.shape[:-1], cfg.num_kv_heads, hd)
    return k, v


def decode_attend_fullkv(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                         cache_len: jax.Array, *, window: int = 0
                         ) -> jax.Array:
    """One-token attention over an explicit cache (FullKV baseline path).

    q [Hq,hd]; k_cache/v_cache [T,Hkv,hd] (post-RoPE); cache_len scalar.
    """
    t, hkv, hd = k_cache.shape
    hq = q.shape[0]
    g = hq // hkv
    qh = q.reshape(hkv, g, hd)
    s = jnp.einsum("hgd,thd->hgt", qh.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) / jnp.sqrt(float(hd))
    pos = jnp.arange(t)
    valid = pos < cache_len
    if window > 0:
        valid &= pos > cache_len - 1 - window
    s = jnp.where(valid[None, None, :], s, NEG_INF)
    pr = jax.nn.softmax(s, axis=-1)
    pr = jnp.where(valid[None, None, :], pr, 0.0)
    out = jnp.einsum("hgt,thd->hgd", pr, v_cache.astype(jnp.float32))
    return out.reshape(hq, hd).astype(q.dtype)
