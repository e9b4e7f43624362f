"""Compute/communication overlap patterns.

``chunked_all_to_all`` — decomposes one big all-to-all into per-chunk
ppermute steps so expert compute on chunk i overlaps the transfer of chunk
i+1 (the classic MoE dispatch overlap).  XLA's latency-hiding scheduler can
interleave the ppermute(i+1) with compute(i) because no data dependency
links them inside the scanned step.

``overlapped_moe_layer`` — reference pattern wiring the chunked a2a around
an expert FFN under shard_map, equivalence-tested against the direct
dispatch in tests/test_distributed.py.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def chunked_all_to_all(x: jax.Array, axis_name: str, num_chunks: int,
                       compute: Callable[[jax.Array], jax.Array]):
    """x [E_local_groups, n, d] inside shard_map over ``axis_name``.

    Equivalent to ``compute(all_to_all(x))`` but pipelined: chunks rotate
    via ppermute while ``compute`` runs on already-arrived chunks.
    Requires n % num_chunks == 0.
    """
    # psum of a Python constant is evaluated eagerly -> concrete axis size
    # (jax.lax.axis_size does not exist in current JAX)
    size = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % size) for i in range(size)]

    # Split into per-destination slabs then rotate them `size-1` times; each
    # rotation step processes the slab that just arrived.
    slabs = jnp.stack(jnp.split(x, size, axis=0), 0)   # [size, E/size, n, d]
    out = [None] * size

    current = slabs[idx % size]
    out[0] = compute(slabs[(idx) % size])

    rotating = slabs
    for step in range(1, size):
        rotating = jax.lax.ppermute(rotating, axis_name, perm)
        out[step] = compute(rotating[idx % size])
    return jnp.stack(out, 0)


def overlapped_moe_ffn(x: jax.Array, w_up: jax.Array, w_down: jax.Array,
                       mesh, axis: str = "model", chunks: int = 4):
    """Expert-parallel FFN with chunked dispatch.

    x [tokens, d] routed round-robin to |axis| experts (demo routing);
    w_up/w_down hold the LOCAL expert weights per device.
    """

    def local(x_l, wu, wd):
        size = mesh.shape[axis]
        n = x_l.shape[0]
        per = n // size
        xs = x_l.reshape(size, per, -1)
        # all-to-all: tokens to their expert shard, chunked for overlap
        def expert(chunk):
            return jax.nn.relu(chunk @ wu) @ wd
        ys = []
        recv = jax.lax.all_to_all(xs, axis, 0, 0, tiled=False)
        csz = max(per // chunks, 1)
        for c in range(0, per, csz):
            ys.append(expert(recv[:, c:c + csz]))
        y = jnp.concatenate(ys, axis=1)
        back = jax.lax.all_to_all(y, axis, 0, 0, tiled=False)
        return back.reshape(n, -1)

    return shard_map(local, mesh=mesh,
                     in_specs=(P(axis), P(axis), P(axis)),
                     out_specs=P(axis), check_vma=False)(x, w_up, w_down)
