"""Ring (context-parallel) attention: equivalence vs dense attention.

Runs in a flagged subprocess with 8 CPU devices (same pattern as
test_distributed.py).
"""
import pytest

from conftest import has_mesh_devices, run_in_mesh_subprocess

if not has_mesh_devices():
    @pytest.mark.parametrize("dummy", [0])
    def test_ring_attention_suite(dummy):
        run_in_mesh_subprocess(__file__, timeout=1200)
else:
    import jax
    from jax.sharding import AxisType
    import jax.numpy as jnp
    import numpy as np

    from repro.distributed.ring_attention import ring_attention
    from repro.layers.attention import _dense_attention

    def _run(mesh_shape, names, b, s, hq, hkv, d, seed=0):
        mesh = jax.make_mesh(mesh_shape, names,
                             axis_types=(AxisType.Auto,) * len(names))
        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.standard_normal((b, s, hq, d)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
        ref = _dense_attention(q, k, v, causal=True, window=0)
        with mesh:
            out = jax.jit(lambda a, b_, c: ring_attention(a, b_, c, mesh))(
                q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=3e-5, atol=3e-5)

    def test_ring_indivisible_heads():
        # 7 q heads over an 8-way ring: the case GSPMD cannot head-shard
        _run((8,), ("model",), 2, 256, 7, 1, 32)

    def test_ring_gqa():
        _run((8,), ("model",), 2, 256, 8, 2, 32)

    def test_ring_data_model_mesh():
        _run((2, 4), ("data", "model"), 4, 128, 7, 1, 32)

    def test_ring_mha():
        _run((4, 2), ("data", "model"), 4, 64, 6, 6, 16, seed=3)
