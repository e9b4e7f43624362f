"""Plain reference for decoder-only models with window and full attention
layers and routed experts, of which this chip holds a share
(Mellum2-12B-A2.5B cut as its configuration file says).

Straightforward ``jax.numpy`` at float32 with ``Precision.HIGHEST`` on
every matrix product.  It imports nothing of the system under test.

The block (the Qwen3-MoE decoder layer whose keys the configuration
carries): ``h += Wo attn(rope(rms_head(Wq x)), rope(rms_head(Wk x)), Wv
x)`` with ``x = rms(h)``, then ``h += moe(rms(h))``.  q and k are
RMS-normed per head before rope.  A window layer's query at position p
sees positions ``p - W + 1 .. p`` (Hugging Face's ``kv_idx > q_idx -
sliding_window``) and uses plain rope; a full layer sees every earlier
position and uses YaRN (inverse frequencies blended between
interpolation by ``factor`` and extrapolation over a ramp set by
``beta_fast``/``beta_slow``, cos and sin scaled by ``attention_factor``).
The router is a softmax over every published expert, top-k,
renormalised; only the experts this chip holds are computed, each on
the tokens routed to it weighted by its gate, and what the other experts
would add is left out, as the engine leaves it out.

Weights.  :func:`init_weights` makes every weight from the seed in one
jitted call, on the device, in float32 (the precision the engine serves
today), in the layout the engine reads (stacked ``[L, ...]`` leaves
under ``layers``; experts ``[L, held, ...]``).  Matrices are fan-in
scaled, but for the router, whose logits get the spread the
configuration assumes (``assumed.router_logit_std``): at fan-in scale
the softmax over 64 experts is near uniform, the 8th and 9th logits lie
~0.08 apart and the 8th gate is ~0.08, where a trained router puts most
of its mass on its top-k.

What it computes.  Like ``dense_gqa``, the served decode tick is
"attention-late": each fed token's queries, keys and values of every
layer come from a trunk that applies only the expert residuals, and the
attention outputs join the stream after the trunk.  The prompt runs the
sequential forward with exact causal attention (window-masked in window
layers).  Each fed token ``s_i`` (position ``P + i``) then runs the
trunk: a window layer attends the last ``W`` positions, which this
reference works out itself; a full layer attends the positions the
caller says its cache holds, plus every position from ``buf_start`` on.
``held`` has one row per FULL layer, in model order.

The control.  ``precision="fp8"`` rounds both operands of every matrix
product to float8 e4m3 (clipped to its range), the step below the bf16
the configuration states.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0
Q_BLOCK = 256          # query rows per attention block
ROW_BLOCK = 16         # compared rows are padded to a multiple of this
WINDOW_TYPE = "sliding_attention"


def dims(cfg: dict) -> dict:
    """Sizes from a configuration file (Hugging Face key names); every
    value hashable."""
    L = cfg["num_hidden_layers"]
    hq = cfg["num_attention_heads"]
    published, held = cfg["reduced"]["num_experts"]
    e0 = int(cfg["held_experts"][0])
    assert cfg["held_experts"][1] - e0 == held == cfg["num_experts"]
    ropes = cfg["rope_parameters"]
    full = ropes["full_attention"]
    return {"L": L, "d": cfg["hidden_size"], "hq": hq,
            "hkv": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "ff": cfg["moe_intermediate_size"], "V": cfg["vocab_size"],
            "E": published, "e0": e0, "held": held,
            "k": cfg["num_experts_per_tok"],
            "norm_topk": bool(cfg["norm_topk_prob"]),
            "eps": float(cfg["rms_norm_eps"]),
            "W": cfg["sliding_window"],
            "window": tuple(t == WINDOW_TYPE
                            for t in cfg["layer_types"][:L]),
            "theta_w": float(ropes["sliding_attention"]["rope_theta"]),
            "yarn": (float(full["rope_theta"]), float(full["factor"]),
                     int(full["original_max_position_embeddings"]),
                     float(full["beta_fast"]), float(full["beta_slow"]),
                     float(full["attention_factor"]))}


def _leaf_shapes(cfg: dict) -> dict:
    m = dims(cfg)
    L, d, hq, hkv, hd, ff, V, E, n = (m[k] for k in (
        "L", "d", "hq", "hkv", "hd", "ff", "V", "E", "held"))
    return {"embed": {"embedding": (V, d), "lm_head": (d, V)},
            "layers": {"attn": {"wq": (L, d, hq * hd),
                                "wk": (L, d, hkv * hd),
                                "wv": (L, d, hkv * hd),
                                "wo": (L, hq * hd, d),
                                "q_norm": (L, hd), "k_norm": (L, hd)},
                       "moe": {"router": (L, d, E),
                               "w_gate": (L, n, d, ff),
                               "w_up": (L, n, d, ff),
                               "w_down": (L, n, ff, d)},
                       "norm1": {"scale": (L, d)},
                       "norm2": {"scale": (L, d)}},
            "final_norm": {"scale": (d,)}}


def _scale_of(path: str, shape, router_std: float) -> tuple:
    """(mean, std) of a leaf: fan-in scaled matrices (the router's
    logits at ``router_std`` for unit-RMS inputs), unit embedding, norm
    scales near one."""
    name = path.split("/")[-1]
    if name == "embedding":
        return 0.0, 1.0
    if name in ("scale", "q_norm", "k_norm"):
        return 1.0, 0.1
    fan_in = float(shape[-2]) ** -0.5
    return 0.0, router_std * fan_in if name == "router" else fan_in


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, 64-bit ones included."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


@functools.lru_cache(maxsize=None)
def _init_fn(shapes_items: tuple, router_std: float):
    @jax.jit
    def init(key):
        out = {}
        for i, (path, shape) in enumerate(shapes_items):
            mean, std = _scale_of(path, shape, router_std)
            k = jax.random.fold_in(key, i)
            out[path] = mean + std * jax.random.normal(k, shape, jnp.float32)
        return out
    return init


def init_weights(cfg: dict, seed: int) -> dict:
    """Every weight from ``seed``, in one jitted call on the device."""
    flat = {}

    def walk(prefix, tree):
        for k, v in tree.items():
            p = f"{prefix}/{k}" if prefix else k
            if isinstance(v, dict):
                walk(p, v)
            else:
                flat[p] = tuple(v)
    walk("", _leaf_shapes(cfg))
    made = _init_fn(tuple(sorted(flat.items())),
                    float(cfg["assumed"]["router_logit_std"]))(
                        seed_key(seed))
    out: dict = {}
    for path, arr in made.items():
        node = out
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = arr
    return out


# ---------------------------------------------------------------------------
# the forward
# ---------------------------------------------------------------------------

def _round(x, precision: str):
    if precision == "fp8":
        x = jnp.clip(x, -FP8_MAX, FP8_MAX).astype(jnp.float8_e4m3fn)
        return x.astype(jnp.float32)
    return x


def _mm(a, b, precision: str):
    return jnp.matmul(_round(a, precision), _round(b, precision),
                      precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def yarn_inv_freq(hd: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """Hugging Face's ``_compute_yarn_parameters`` (``truncate`` on)."""
    pos_freqs = theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    extra, inter = 1.0 / pos_freqs, 1.0 / (factor * pos_freqs)

    def corr(rot):
        return hd * math.log(original / (rot * 2 * math.pi)) / \
            (2 * math.log(theta))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), hd - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(hd // 2) - low) / (high - low), 0.0, 1.0)
    extra_share = 1.0 - ramp
    return inter * (1 - extra_share) + extra * extra_share


def _rope(x, pos, window: bool, m):
    """Rotate-half rope of the layer's kind; x [T, H, hd], pos [T]."""
    hd = x.shape[-1]
    half = hd // 2
    if window:
        inv = m["theta_w"] ** (-jnp.arange(half, dtype=jnp.float32) / half)
        scale = 1.0
    else:
        inv = jnp.asarray(yarn_inv_freq(hd, *m["yarn"][:5]), jnp.float32)
        scale = m["yarn"][5]
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos = (jnp.cos(ang) * scale)[:, None]
    sin = (jnp.sin(ang) * scale)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _qkv(lp, x, pos, window, m, precision):
    a = lp["attn"]
    q, k, v = (_mm(x, a[w], precision) for w in ("wq", "wk", "wv"))
    T = x.shape[0]
    q = _rms(q.reshape(T, m["hq"], m["hd"]), a["q_norm"], m["eps"])
    k = _rms(k.reshape(T, m["hkv"], m["hd"]), a["k_norm"], m["eps"])
    return (_rope(q, pos, window, m), _rope(k, pos, window, m),
            v.reshape(T, m["hkv"], m["hd"]))


def _attend(q, k, v, visible, m, precision):
    """q [Tq, Hq, hd] over k/v [Tk, Hkv, hd]; ``visible(qi) -> [B, Tk]``
    for a block of query indices.  Exact softmax, query blocks of
    ``Q_BLOCK`` rows."""
    Tq = q.shape[0]
    g = m["hq"] // m["hkv"]
    nb = Tq // Q_BLOCK
    qb = q.reshape(nb, Q_BLOCK, m["hkv"], g, m["hd"])

    def one(args):
        qblk, qi = args
        s = jnp.einsum("qhgd,khd->hgqk", _round(qblk, precision),
                       _round(k, precision), precision=HIGHEST)
        s = s / np.sqrt(m["hd"])
        s = jnp.where(visible(qi)[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hgqk,khd->qhgd", _round(p, precision),
                       _round(v, precision), precision=HIGHEST)
        return o.reshape(Q_BLOCK, m["hq"] * m["hd"])

    qidx = jnp.arange(Tq).reshape(nb, Q_BLOCK)
    return jax.lax.map(one, (qb, qidx)).reshape(Tq, m["hq"] * m["hd"])


def _moe(lp, x, m, precision):
    """The held experts' part: softmax router over all ``E`` experts,
    top-k (renormalised), each held expert applied to every token and
    weighted by the token's gate for it (zero where not chosen)."""
    p = lp["moe"]
    probs = jax.nn.softmax(_mm(x, p["router"], precision), -1)
    gate, idx = jax.lax.top_k(probs, m["k"])
    if m["norm_topk"]:
        gate = gate / jnp.sum(gate, -1, keepdims=True)
    held = jnp.arange(m["e0"], m["e0"] + m["held"])
    w = jnp.sum(jnp.where(idx[:, :, None] == held, gate[:, :, None], 0.0),
                axis=1)                                      # [T, held]
    mm = functools.partial(jnp.einsum, precision=HIGHEST)
    r = functools.partial(_round, precision=precision)
    hg = mm("td,edf->tef", r(x), r(p["w_gate"]))
    hu = mm("td,edf->tef", r(x), r(p["w_up"]))
    ye = mm("tef,efd->ted", r(jax.nn.silu(hg) * hu), r(p["w_down"]))
    return jnp.sum(ye * w[:, :, None], axis=1)


@functools.lru_cache(maxsize=None)
def _final_hidden_fn(m_items: tuple, precision: str):
    m = dict(m_items)

    @jax.jit
    def run(w, prompt, n_prompt, fed, n_fed, held, buf_start):
        """prompt [Pp] (first ``n_prompt`` real), fed [Np] (first
        ``n_fed`` real; row i feeds token s_i), held [L_full, Pp + Np]
        (key positions, prompt then fed, each full layer's cache holds).
        Returns the final normed hidden state after each fed token
        [Np, d]."""
        Pp, Np = prompt.shape[0], fed.shape[0]
        pos_p = jnp.arange(Pp)
        pos_f = n_prompt + jnp.arange(Np)
        kidx = jnp.arange(Pp + Np)
        kpos = jnp.concatenate([pos_p, pos_f])
        real = (kidx < n_prompt) | ((kidx >= Pp) & (kidx - Pp < n_fed))
        hp = jnp.take(w["embed"]["embedding"], prompt, axis=0)
        hs = jnp.take(w["embed"]["embedding"], fed, axis=0)
        att = jnp.zeros_like(hs)
        n_full = 0
        for l, window in enumerate(m["window"]):
            lp = jax.tree.map(lambda x: x[l], w["layers"])

            def prompt_visible(qi, window=window):
                kv = kidx[:Pp]
                ok = (kv[None, :] <= qi[:, None]) & (kv[None, :] < n_prompt)
                if window:
                    ok &= kv[None, :] > qi[:, None] - m["W"]
                return ok

            xp = _rms(hp, lp["norm1"]["scale"], m["eps"])
            qp, kp, vp = _qkv(lp, xp, pos_p, window, m, precision)
            op = _attend(qp, kp, vp, prompt_visible, m, precision)
            hp = hp + _mm(op, lp["attn"]["wo"], precision)
            hp = hp + _moe(lp, _rms(hp, lp["norm2"]["scale"], m["eps"]), m,
                           precision)

            if window:
                kept = jnp.ones_like(real)
            else:
                kept = held[n_full] | (kpos >= buf_start)
                n_full += 1

            def fed_visible(qi, window=window, kept=kept):
                qpos = (n_prompt + qi)[:, None]
                ok = (kpos[None, :] <= qpos) & (real & kept)[None, :]
                if window:
                    ok &= kpos[None, :] > qpos - m["W"]
                return ok

            xs_ = _rms(hs, lp["norm1"]["scale"], m["eps"])
            qs, ks, vs = _qkv(lp, xs_, pos_f, window, m, precision)
            os_ = _attend(qs, jnp.concatenate([kp, ks]),
                          jnp.concatenate([vp, vs]), fed_visible, m,
                          precision)
            att = att + _mm(os_, lp["attn"]["wo"], precision)
            hs = hs + _moe(lp, _rms(hs, lp["norm2"]["scale"], m["eps"]), m,
                           precision)
        return _rms(hs + att, w["final_norm"]["scale"], m["eps"])

    return run


@functools.lru_cache(maxsize=None)
def _gaps_fn(control: bool):
    @jax.jit
    def gaps(w, h_ref, h_ctl, targets):
        """Per row: the reference's best logit minus its logit at the
        served token, and (``control``) minus its logit at the token the
        fp8 control ranks first."""
        head = w["embed"]["lm_head"]
        lr = _mm(h_ref, head, "f32")
        best = lr.max(-1)
        served = jnp.take_along_axis(lr, targets[:, None], -1)[:, 0]
        if not control:
            return best - served, jnp.zeros_like(best)
        top = jnp.argmax(_mm(h_ctl, head, "fp8"), -1)
        ctl = jnp.take_along_axis(lr, top[:, None], -1)[:, 0]
        return best - served, best - ctl
    return gaps


def _bucket(n: int, least: int) -> int:
    b = least
    while b < n:
        b *= 2
    return b


def served_gaps(cfg: dict, w: dict, prompt: np.ndarray, fed: np.ndarray,
                held: np.ndarray, buf_start: int, rows: np.ndarray,
                targets: np.ndarray, prompt_pad: int, control: bool = False):
    """Gaps at chosen rows of one request, teacher-forced.

    ``fed`` are the served tokens fed back (``s_0 .. s_{k-1}``); ``held``
    ``[L_full, P + k]`` marks the positions each full layer's cache
    holds; positions from ``buf_start`` on are always attended.  ``rows``
    are indices ``i`` into ``fed`` whose next-token logits are compared
    with ``targets`` (the tokens served there).  ``prompt_pad`` fixes the
    padded prompt length; the fed length is padded to a power of two.
    Returns ``(gap_served, gap_control)`` as numpy, one per row; the
    second is zeros unless ``control``."""
    m = dims(cfg)
    n_full = sum(not x for x in m["window"])
    k, P = len(fed), len(prompt)
    Np = _bucket(max(k, 1), Q_BLOCK)
    pp = np.zeros(prompt_pad, np.int32)
    pp[:P] = prompt
    fp = np.zeros(Np, np.int32)
    fp[:k] = fed
    hk = np.zeros((n_full, prompt_pad + Np), bool)
    hk[:, :P] = held[:, :P]
    hk[:, prompt_pad:prompt_pad + k] = held[:, P:P + k]
    items = tuple(sorted(m.items()))
    args = (w, pp, P, fp, k, hk, int(buf_start))
    h_ref = _final_hidden_fn(items, "f32")(*args)
    h_ctl = _final_hidden_fn(items, "fp8")(*args) if control else h_ref
    n = len(rows)
    R = _bucket(max(n, 1), ROW_BLOCK)
    idx = np.zeros(R, np.int32)
    idx[:n] = rows
    tgt = np.zeros(R, np.int32)
    tgt[:n] = targets
    a, b = _gaps_fn(control)(w, jnp.take(h_ref, idx, axis=0),
                             jnp.take(h_ctl, idx, axis=0), tgt)
    return np.asarray(a)[:n], np.asarray(b)[:n]
