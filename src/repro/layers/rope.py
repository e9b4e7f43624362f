"""Rotary position embeddings (llama rotate-half convention), plain or
YaRN-scaled (:func:`rope_table`)."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("head_dim",))
def rope_freqs(positions: jax.Array, head_dim: int,
               theta: float = 1e4) -> tuple[jax.Array, jax.Array]:
    """positions [...,] -> (cos, sin) each [..., head_dim//2]."""
    half = head_dim // 2
    inv = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x [..., heads, head_dim]; cos/sin broadcast against x[..., :d//2].

    rotate-half: (x1, x2) -> (x1*cos - x2*sin, x2*cos + x1*sin).
    """
    d = x.shape[-1]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    if cos.ndim == x.ndim - 1:          # add heads axis
        cos = cos[..., None, :]
        sin = sin[..., None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def rope_single(x: jax.Array, position: jax.Array, theta: float) -> jax.Array:
    """x [heads, head_dim] at a scalar position."""
    cos, sin = rope_freqs(position, x.shape[-1], theta)
    return apply_rope(x, cos[None, :], sin[None, :])


def yarn_inv_freq(head_dim: int, theta: float, factor: float,
                  original_max_position: int, beta_fast: float,
                  beta_slow: float) -> jax.Array:
    """YaRN inverse frequencies [head_dim // 2] (Hugging Face's
    ``_compute_yarn_parameters`` with ``truncate``): dimensions that turn
    fewer than ``beta_slow`` times over the original context are
    interpolated by ``factor``, those that turn more than ``beta_fast``
    times keep their frequency, and a linear ramp blends between.
    Computed on the host in Python floats, handed over as float32."""
    def dim_of(rotations):
        return (head_dim * math.log(original_max_position
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    inv = []
    for i in range(head_dim // 2):
        extra = theta ** (-2 * i / head_dim)
        keep = 1.0 - min(max((i - low) / (high - low), 0.0), 1.0)
        inv.append(extra / factor * (1.0 - keep) + extra * keep)
    return jnp.asarray(inv, jnp.float32)


def rope_table(positions: jax.Array, head_dim: int, rc
               ) -> tuple[jax.Array, jax.Array]:
    """(cos, sin) [..., head_dim // 2] of a layer kind's rope
    (``config.RopeConfig``); YaRN scales both by its attention factor."""
    if rc.kind == "default":
        return rope_freqs(positions, head_dim, rc.theta)
    inv = yarn_inv_freq(head_dim, rc.theta, rc.factor,
                        rc.original_max_position, rc.beta_fast, rc.beta_slow)
    ang = positions.astype(jnp.float32)[..., None] * inv
    return (jnp.cos(ang) * rc.attention_factor,
            jnp.sin(ang) * rc.attention_factor)
