"""Compile the main path's kernels for a described TPU v5e at qwen2-7b
widths (28 query heads, 4 kv heads, head_dim 128), and the fused decode
kernel at yi-6b's too (32 over 4), with no chip attached.

Nothing runs: a compile that passes says the TPU compiler accepts the
kernels' block shapes, layouts and memory use.  The topology is described
inside a fixture, never at import time, and the compiles run in this test
process with the persistent compilation cache off.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.ct_paged_attention import (ct_paged_attention_batched,
                                              ct_paged_attention_fused)
from repro.kernels.flash_prefill import flash_prefill

L, R, H, GQ, D, NB, BS, G = 4, 4, 4, 7, 128, 128, 16, 16
NP = R * NB
PREFILL = 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _fused_args(s, heads, gq=GQ):
    return (s((L, R, heads, gq, D), jnp.float32),
            s((L, NP, heads, BS, D), jnp.uint8),
            s((L, NP, heads, BS, D), jnp.uint8),
            s((L, NP, heads, BS, D // 16), jnp.bfloat16),
            s((L, NP, heads, BS, D // 16), jnp.bfloat16),
            s((L, R, NB, BS), jnp.uint8), s((L, R, NB, BS), jnp.uint8),
            s((R, L, NB), jnp.int32),
            s((L, R, heads, G, D), jnp.bfloat16),
            s((L, R, heads, G, D), jnp.bfloat16), s((R,), jnp.int32))


def _batched_args(s):
    # a 128-token prefill chunk folded into the q-group axis
    return (s((1, H, PREFILL * GQ, D), jnp.float32),
            s((NP, H, BS, D), jnp.uint8), s((NP, H, BS, D), jnp.uint8),
            s((NP, H, BS, D // 16), jnp.bfloat16),
            s((NP, H, BS, D // 16), jnp.bfloat16),
            s((1, NB, BS), jnp.uint8), s((1, NB, BS), jnp.uint8),
            s((1, NB), jnp.int32))


def _flash_args(s):
    return (s((PREFILL, H * GQ, D), jnp.float32),
            s((PREFILL, H, D), jnp.float32),
            s((PREFILL, H, D), jnp.float32))


CASES = {
    # the decode tick: every layer and slot in one launch
    "fused": (lambda *a: ct_paged_attention_fused(*a, group=16),
              lambda s: _fused_args(s, H)),
    # one tensor-parallel shard of the tick at --mesh model=4
    "fused_one_head": (lambda *a: ct_paged_attention_fused(*a, group=16),
                       lambda s: _fused_args(s, 1)),
    # yi-6b's 32 query heads over 4 kv heads: another q-group width
    "fused_yi6b": (lambda *a: ct_paged_attention_fused(*a, group=16),
                   lambda s: _fused_args(s, H, gq=8)),
    # big-chunk prefill: frozen-pool partition
    "batched_prefill_fold": (
        lambda *a: ct_paged_attention_batched(*a, group=16), _batched_args),
    # big-chunk prefill: causal intra-chunk partition with flash stats
    "flash_prefill_stats": (
        lambda q, k, v: flash_prefill(q, k, v, causal=True,
                                      return_stats=True), _flash_args),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, make_args = CASES[case]

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(fn).lower(*make_args(s)).compile()
    assert "tpu_custom_call" in compiled.as_text()
