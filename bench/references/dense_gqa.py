"""Plain reference for dense decoder-only GQA models (Qwen2, Llama/Yi).

Straightforward ``jax.numpy`` at float32 with ``Precision.HIGHEST`` on
every matrix product.  It imports nothing of the system under test.

Weights.  :func:`init_weights` makes every weight from the seed in one
jitted call, on the device, in float32 (the precision the engine serves
today).  The layout (stacked ``[L, ...]`` layer leaves under ``layers``)
is the one the engine's forward reads, so the same arrays feed both.

What it computes.  The served model's decode tick is "attention-late":
within a tick every layer's query, key and value come from a trunk that
applies only the MLP residuals, and the attention outputs of all layers
join the residual stream after the trunk.  Prefill is the ordinary
sequential transformer.  This reference computes exactly that function
at full precision: the prompt runs the sequential forward with exact
causal attention; each fed token ``s_i`` (at position ``P + i``) runs the
trunk, and its attention in layer ``l`` covers the positions the caller
says layer ``l`` holds, plus every position from ``buf_start`` on (the
uncompressed buffer of the current group, the token itself included).
The summed attention outputs join before the final norm, and the logits
after feeding ``s_i`` choose ``s_{i+1}``.  Which positions are held is
the only thing taken from outside; every key, value and logit is
computed here from the weights.

The control.  ``precision="fp8"`` rounds both operands of every matrix
product to float8 e4m3 (clipped to its range), the step below the bf16
the configurations state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0
Q_BLOCK = 256          # query rows per attention block
ROW_BLOCK = 16         # compared rows are padded to a multiple of this


def dims(cfg: dict) -> dict:
    """Sizes from a configuration file (Hugging Face key names)."""
    hq = cfg["num_attention_heads"]
    return {"L": cfg["num_hidden_layers"], "d": cfg["hidden_size"],
            "hq": hq, "hkv": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim", cfg["hidden_size"] // hq),
            "ff": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "bias": bool(cfg["assumed"]["attention_bias"]),
            "eps": float(cfg["rms_norm_eps"]),
            "theta": float(cfg["rope_theta"])}


def _leaf_shapes(cfg: dict) -> dict:
    m = dims(cfg)
    L, d, hq, hkv, hd, ff, V = (m[k] for k in
                                ("L", "d", "hq", "hkv", "hd", "ff", "V"))
    attn = {"wq": (L, d, hq * hd), "wk": (L, d, hkv * hd),
            "wv": (L, d, hkv * hd), "wo": (L, hq * hd, d)}
    if m["bias"]:
        attn.update(bq=(L, hq * hd), bk=(L, hkv * hd), bv=(L, hkv * hd))
    return {"embed": {"embedding": (V, d), "lm_head": (d, V)},
            "layers": {"attn": attn,
                       "mlp": {"w_gate": (L, d, ff), "w_up": (L, d, ff),
                               "w_down": (L, ff, d)},
                       "norm1": {"scale": (L, d)},
                       "norm2": {"scale": (L, d)}},
            "final_norm": {"scale": (d,)}}


def _scale_of(path: str, shape) -> tuple:
    """(mean, std) of a leaf: fan-in scaled matrices, unit embedding,
    small biases, norm scales near one."""
    name = path.split("/")[-1]
    if name == "embedding":
        return 0.0, 1.0
    if name.startswith("b"):
        return 0.0, 0.1
    if name == "scale":
        return 1.0, 0.1
    return 0.0, float(shape[-2]) ** -0.5


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, 64-bit ones included."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


@functools.lru_cache(maxsize=None)
def _init_fn(shapes_items: tuple):
    @jax.jit
    def init(key):
        out = {}
        for i, (path, shape) in enumerate(shapes_items):
            mean, std = _scale_of(path, shape)
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
    made = _init_fn(tuple(sorted(flat.items())))(seed_key(seed))
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


def _rope(x, pos, theta):
    """Rotate-half RoPE; x [T, H, hd], pos [T]."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _qkv(lp, x, pos, m, precision):
    a = lp["attn"]
    q, k, v = (_mm(x, a[w], precision) for w in ("wq", "wk", "wv"))
    if m["bias"]:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    T = x.shape[0]
    q = _rope(q.reshape(T, m["hq"], m["hd"]), pos, m["theta"])
    k = _rope(k.reshape(T, m["hkv"], m["hd"]), pos, m["theta"])
    return q, k, v.reshape(T, m["hkv"], m["hd"])


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


def _mlp(lp, x, m, precision):
    p = lp["mlp"]
    h = jax.nn.silu(_mm(x, p["w_gate"], precision)) * \
        _mm(x, p["w_up"], precision)
    return _mm(h, p["w_down"], precision)


@functools.lru_cache(maxsize=None)
def _final_hidden_fn(m_items: tuple, precision: str):
    m = dict(m_items)

    @jax.jit
    def run(w, prompt, n_prompt, fed, n_fed, held, buf_start):
        """prompt [Pp] (first ``n_prompt`` real), fed [Np] (first
        ``n_fed`` real; row i feeds token s_i), held [L, Pp + Np] (key
        positions, prompt then fed, that each layer's cache holds).
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

        def prompt_visible(qi):
            kv = kidx[:Pp]
            return (kv[None, :] <= qi[:, None]) & (kv[None, :] < n_prompt)

        def layer(carry, xs):
            lp, held_l = xs
            hp, hs, att = carry
            xp = _rms(hp, lp["norm1"]["scale"], m["eps"])
            qp, kp, vp = _qkv(lp, xp, pos_p, m, precision)
            op = _attend(qp, kp, vp, prompt_visible, m, precision)
            hp = hp + _mm(op, lp["attn"]["wo"], precision)
            hp = hp + _mlp(lp, _rms(hp, lp["norm2"]["scale"], m["eps"]), m,
                           precision)

            def fed_visible(qi):
                causal = kpos[None, :] <= (n_prompt + qi)[:, None]
                kept = held_l | (kpos >= buf_start)
                return causal & (real & kept)[None, :]

            xs_ = _rms(hs, lp["norm1"]["scale"], m["eps"])
            qs, ks, vs = _qkv(lp, xs_, pos_f, m, precision)
            os_ = _attend(qs, jnp.concatenate([kp, ks]),
                          jnp.concatenate([vp, vs]), fed_visible, m,
                          precision)
            att = att + _mm(os_, lp["attn"]["wo"], precision)
            hs = hs + _mlp(lp, _rms(hs, lp["norm2"]["scale"], m["eps"]), m,
                           precision)
            return (hp, hs, att), None

        (hp, hs, att), _ = jax.lax.scan(
            layer, (hp, hs, jnp.zeros_like(hs)), (w["layers"], held))
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
    ``[L, P + k]`` marks the positions each layer's cache holds;
    positions from ``buf_start`` on are always attended.  ``rows`` are
    indices ``i`` into ``fed`` whose next-token logits are compared with
    ``targets`` (the tokens served there).  ``prompt_pad`` fixes the
    padded prompt length (one compiled shape per traffic mix); the fed
    length is padded to a power of two, so a run compiles few shapes.
    Returns ``(gap_served, gap_control)`` as numpy, one per row; the
    second is zeros unless ``control``."""
    m = dims(cfg)
    k, P = len(fed), len(prompt)
    Np = _bucket(max(k, 1), Q_BLOCK)
    pp = np.zeros(prompt_pad, np.int32)
    pp[:P] = prompt
    fp = np.zeros(Np, np.int32)
    fp[:k] = fed
    hk = np.zeros((m["L"], prompt_pad + Np), bool)
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
