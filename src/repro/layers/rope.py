"""Rotary position embeddings (llama rotate-half convention)."""
from __future__ import annotations

import functools

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
