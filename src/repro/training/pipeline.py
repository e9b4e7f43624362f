"""GPipe-style pipeline parallelism over the ``pod`` axis.

The multi-pod mesh's ``pod`` axis can act as DP (default) or as PP: layer
blocks shard across pods, microbatches stream through with ppermute
hand-offs.  This is the circular-pipeline formulation (praxis-style): all
stages compute every tick on different microbatches; bubbles are the usual
(S-1)/(M+S-1) fraction.

The transformation is generic over a ``stage_fn(stage_params, h) -> h``;
equivalence against the unpipelined model is tested on a CPU mesh in
tests/test_distributed.py.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def pipeline_apply(stage_fn: Callable, stage_params, h0: jax.Array,
                   mesh, *, num_microbatches: int, axis: str = "pod"
                   ) -> jax.Array:
    """Run ``h -> stage_fn^S(h)`` with stages sharded over ``axis``.

    Args:
      stage_params: pytree with leading [S] axis (S == |axis|), sharded on
        ``axis``.
      h0: [M, mb, ...] microbatched activations (replicated).
    Returns [M, mb, ...] outputs after all S stages.
    """
    s_axis = mesh.shape[axis]
    m = num_microbatches
    assert h0.shape[0] == m

    def local(params_l, h_all):
        # params_l: this stage's params ([1, ...] slab); h_all [M, mb, ...]
        stage = jax.lax.axis_index(axis)
        size = s_axis     # static mesh axis size (jax.lax has no axis_size)
        params_me = jax.tree.map(lambda x: x[0], params_l)
        ticks = m + size - 1
        perm = [(i, (i + 1) % size) for i in range(size)]

        buf = jnp.zeros_like(h_all)            # outputs per microbatch
        carry = jnp.zeros_like(h_all[0])       # inbound activation

        def tick(state, t):
            carry, buf = state
            mb_idx = t - stage                 # microbatch this stage sees
            active = (mb_idx >= 0) & (mb_idx < m)
            # stage 0 ingests fresh microbatches; others use carried input
            inp = jnp.where(stage == 0,
                            h_all[jnp.clip(t, 0, m - 1)], carry)
            out = stage_fn(params_me, inp)
            out = jnp.where(active, out, carry)
            # last stage records finished microbatches
            buf = jnp.where(
                (stage == size - 1) & active,
                buf.at[jnp.clip(mb_idx, 0, m - 1)].set(out), buf)
            # hand off to the next stage
            nxt = jax.lax.ppermute(out, axis, perm)
            return (nxt, buf), None

        (carry, buf), _ = jax.lax.scan(tick, (carry, buf),
                                       jnp.arange(ticks))
        # only the last stage holds real outputs; broadcast them
        buf = jax.lax.psum(
            jnp.where(stage == size - 1, buf, jnp.zeros_like(buf)), axis)
        return buf

    return shard_map(
        local, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False)(stage_params, h0)
