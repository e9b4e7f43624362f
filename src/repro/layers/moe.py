"""Mixture-of-Experts FFN: a training layer and a serving layer.

:func:`moe_apply` (training) is GShard-style grouped one-hot dispatch.
Tokens are processed in groups of ``dispatch_group`` so the
dispatch/combine einsums stay O(tokens * group * d) instead of quadratic
in the sequence.  Expert weights are stacked [E, ...] and shard over the
``model`` axis (EP); the dispatch einsums lower to all-to-alls under
GSPMD.  Top-k routing (k=2 mixtral, k=1 llama4) with renormalized gates,
capacity dropping, and the standard load-balancing auxiliary loss.

:func:`moe_held` (serving) is dropless and holds a share of the experts
(``MoEConfig.held``): it routes over every expert, and computes exactly
the part of the result its own experts give, with a grouped matmul
(``jax.lax.ragged_dot``) over the tokens sorted by expert.  No capacity,
so a token's output does not depend on the tokens served beside it.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.layers.common import act_fn, dense_init, split_keys


def moe_params(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    """The router over every expert; weights of the held experts only."""
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.moe.num_experts
    n = cfg.moe.num_held
    ks = split_keys(key, 4)
    return {
        "router": dense_init(ks[0], (d, e), scale=0.02, dtype=jnp.float32),
        "w_up": dense_init(ks[1], (n, d, ff), scale=d ** -0.5, dtype=dtype),
        "w_gate": dense_init(ks[2], (n, d, ff), scale=d ** -0.5, dtype=dtype),
        "w_down": dense_init(ks[3], (n, ff, d), scale=ff ** -0.5, dtype=dtype),
    }


def moe_apply(p: dict, x: jax.Array, cfg: ModelConfig
              ) -> Tuple[jax.Array, jax.Array]:
    """x [B, S, D] -> (y [B, S, D], aux_loss scalar)."""
    mcfg = cfg.moe
    e, k = mcfg.num_experts, mcfg.num_experts_per_token
    if mcfg.num_held != e:
        raise NotImplementedError(
            "moe_apply computes every expert; a device holding a share of "
            "them serves through moe_held")
    b, s, d = x.shape
    n = b * s
    gsz = min(mcfg.dispatch_group, n)
    while n % gsz != 0:            # static; dims are powers of two in practice
        gsz -= 1
    ng = n // gsz
    xt = x.reshape(ng, gsz, d)

    logits = xt.astype(jnp.float32) @ p["router"]           # [g, t, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)           # [g, t, k]
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, -1, keepdims=True), 1e-9)

    cap = int(math.ceil(k * gsz / e * mcfg.capacity_factor))
    cap = max(cap, 4)

    sel = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)    # [g, t, k, E]
    # position of each (token, choice) within its expert queue
    pos = jnp.cumsum(sel.reshape(ng, gsz * k, e), axis=1).reshape(
        ng, gsz, k, e) - 1.0
    keep = sel * (pos < cap)
    posc = jnp.clip(pos, 0, cap - 1).astype(jnp.int32)
    disp = keep[..., None] * jax.nn.one_hot(posc, cap,
                                            dtype=jnp.float32)  # [g,t,k,E,C]
    dispatch = jnp.sum(disp, axis=2)                          # [g, t, E, C]
    combine = jnp.sum(disp * gate_vals[..., None, None], axis=2)

    # pin the EP layout: token groups stay data-sharded, expert dims shard
    # over `model` — otherwise GSPMD routes dispatch through all-reduces of
    # the full [g,E,C,D] tensors (§Perf llama4 iteration: 515 GB/step)
    from repro.distributed.sharding import constrain
    # dispatch/expert compute in the model dtype (bf16), router math in f32
    # (§Perf llama4 iteration 3)
    cdt = x.dtype
    dispatch = constrain(dispatch.astype(cdt), "dp", None, "model", None)
    combine = constrain(combine.astype(cdt), "dp", None, "model", None)
    ein = jnp.einsum
    xe = ein("gtec,gtd->gecd", dispatch, xt.astype(cdt))
    xe = constrain(xe, "dp", "model", None, None)
    f = act_fn(cfg.act)
    h = f(ein("gecd,edf->gecf", xe, p["w_gate"].astype(cdt))) * \
        ein("gecd,edf->gecf", xe, p["w_up"].astype(cdt))
    h = constrain(h, "dp", "model", None, None)
    ye = ein("gecf,efd->gecd", h, p["w_down"].astype(cdt))
    ye = constrain(ye, "dp", "model", None, None)
    y = ein("gtec,gecd->gtd", combine, ye)

    # load-balancing aux loss (Switch): E * sum_e f_e * P_e
    frac = jnp.mean(jnp.sum(jax.nn.one_hot(gate_idx[..., 0], e), axis=1)
                    / gsz, axis=0)
    pmean = jnp.mean(probs, axis=(0, 1))
    aux = e * jnp.sum(frac * pmean)
    return y.reshape(b, s, d).astype(x.dtype), aux


def route(router: jax.Array, x: jax.Array, cfg: ModelConfig
          ) -> Tuple[jax.Array, jax.Array]:
    """Softmax router over every expert, top-k, renormalised (as
    :func:`moe_apply` gates): x [T, D] -> (gates [T, k] f32, experts
    [T, k]).  The logits are a true float32 product (``HIGHEST``; the
    TPU's default would round both operands to bf16): the top-k choice
    is discrete, and that rounding alone flips near-tied experts."""
    logits = jnp.matmul(x.astype(jnp.float32), router,
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, cfg.moe.num_experts_per_token)
    return gate / jnp.sum(gate, -1, keepdims=True), idx


def moe_held(p: dict, x: jax.Array, cfg: ModelConfig,
             valid: jax.Array | None = None
             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Dropless expert layer over the experts this device holds.

    x [T, D] -> ``(y [T, D], assigned, hit)``: ``y`` is the held
    experts' part of the layer's output (each routed (token, expert) pair
    on a held expert, weighted by its gate; pairs routed elsewhere add
    nothing); ``assigned`` counts those pairs and ``hit`` the held
    experts that received at least one (int32 scalars).  Tokens with
    ``valid`` [T] False (padding, idle slots) route nowhere.

    The T * k pairs are sorted by held expert (pairs routed elsewhere
    last) and run through one grouped matmul per projection
    (``ragged_dot``); each token's k rows are gathered back and summed
    in a fixed order, so nothing depends on the other tokens."""
    m = cfg.moe
    k = m.num_experts_per_token
    e0, e1 = m.held_range
    n = e1 - e0
    t = x.shape[0]
    gate, idx = route(p["router"], x, cfg)                  # [T, k]
    local = idx - e0
    held = (local >= 0) & (local < n)
    if valid is not None:
        held = held & valid[:, None]
    expert = jnp.where(held, local, n).reshape(-1)          # [T*k]
    order = jnp.argsort(expert, stable=True)
    sizes = jnp.sum(jax.nn.one_hot(expert, n + 1, dtype=jnp.int32),
                    axis=0)[:n]
    rows = x[order // k]                                    # [T*k, D]
    f = act_fn(cfg.act)
    h = f(jax.lax.ragged_dot(rows, p["w_gate"], sizes)) * \
        jax.lax.ragged_dot(rows, p["w_up"], sizes)
    ye = jax.lax.ragged_dot(h, p["w_down"], sizes)          # [T*k, D]
    # rows past the held groups are not computed: zero them
    ye = jnp.where((jnp.arange(t * k) < jnp.sum(sizes))[:, None], ye, 0)
    ye = ye[jnp.argsort(order)].reshape(t, k, -1)           # token order
    w = jnp.where(held, gate, 0.0).astype(ye.dtype)
    y = jnp.sum(ye * w[..., None], axis=1)
    return (y.astype(x.dtype), jnp.sum(held, dtype=jnp.int32),
            jnp.sum(sizes > 0, dtype=jnp.int32))
