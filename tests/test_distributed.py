"""Distributed tests on an 8-device CPU mesh.

pytest collects this file in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (see the module-level
re-exec guard), so the main test process keeps its single-device view.
"""
import pytest

from conftest import has_mesh_devices, run_in_mesh_subprocess

if not has_mesh_devices():
    # Re-exec this module's tests in a flagged subprocess.
    @pytest.mark.parametrize("dummy", [0])
    def test_distributed_suite(dummy):
        run_in_mesh_subprocess(__file__)
else:
    import dataclasses

    import jax
    from jax.sharding import AxisType
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_smoke_config
    from repro.distributed import sharding as SH
    from repro.distributed.compression import (ef_transform, int8_quantize,
                                               int8_dequantize,
                                               make_ef_state,
                                               make_cross_pod_grad_fn)
    from repro.models import build_model
    from repro.training.optimizer import adamw_init
    from repro.training.train_step import make_train_step
    from repro.config import OptimizerConfig

    def _mesh(shape, names):
        return jax.make_mesh(shape, names,
                             axis_types=(AxisType.Auto,) * len(shape))

    def test_eight_devices():
        assert jax.device_count() == 8

    def test_param_specs_divisible():
        cfg = get_smoke_config("yi-6b")
        model = build_model(cfg)
        params = model.init_params(0)
        mesh = _mesh((2, 4), ("data", "model"))
        specs = SH.param_specs(params, mesh)
        for (path, leaf), spec in zip(
                jax.tree_util.tree_flatten_with_path(params)[0],
                jax.tree.leaves(specs,
                                is_leaf=lambda x: isinstance(x, P))):
            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            for dim, ax in zip(leaf.shape, spec):
                if ax is None:
                    continue
                n = np.prod([sizes[a] for a in
                             (ax if isinstance(ax, tuple) else (ax,))])
                assert dim % n == 0, (path, leaf.shape, spec)

    def test_sharded_train_step_matches_single_device(rng=None):
        """1-device vs (2,4)-mesh train step: same loss and params."""
        rng = np.random.default_rng(0)
        cfg = get_smoke_config("yi-6b")
        model = build_model(cfg)
        params = model.init_params(0)
        opt = adamw_init(params)
        batch = {
            "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 16)),
                                  jnp.int32),
            "targets": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 16)),
                                   jnp.int32)}
        step = make_train_step(model.loss, cfg, OptimizerConfig(),
                               remat=True)

        p1, o1, m1 = jax.jit(step)(params, opt, batch)

        mesh = _mesh((2, 4), ("data", "model"))
        psh = SH.param_shardings(params, mesh)
        bsh = SH.to_shardings(SH.train_batch_specs(batch, mesh), mesh)
        params_s = jax.device_put(params, psh)
        opt_s = type(opt)(step=opt.step,
                          m=jax.device_put(opt.m, psh),
                          v=jax.device_put(opt.v, psh))
        batch_s = jax.device_put(batch, bsh)
        with mesh:
            p2, o2, m2 = jax.jit(step)(params_s, opt_s, batch_s)
        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                                   rtol=2e-5)
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(jax.device_get(b),
                                                  np.float32),
                                       rtol=2e-4, atol=2e-5)

    def test_decode_step_sharded_parity():
        """FullKV decode on the mesh (seq-sharded cache) == single device."""
        rng = np.random.default_rng(1)
        cfg = get_smoke_config("yi-6b")
        model = build_model(cfg)
        params = model.init_params(0)
        from repro.serving.serve_step import make_decode_step_fullkv
        step = make_decode_step_fullkv(cfg)
        B, T = 8, 64
        batch = {
            "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B,)),
                                  jnp.int32),
            "positions": jnp.full((B,), 3, jnp.int32),
            "k_cache": jnp.asarray(rng.standard_normal(
                (B, cfg.num_layers, T, cfg.num_kv_heads, cfg.head_dim)),
                jnp.float32),
            "v_cache": jnp.asarray(rng.standard_normal(
                (B, cfg.num_layers, T, cfg.num_kv_heads, cfg.head_dim)),
                jnp.float32),
            "cache_len": jnp.full((B,), 3, jnp.int32),
        }
        lg1 = jax.jit(step)(params, batch)[0]
        mesh = _mesh((2, 4), ("data", "model"))
        psh = SH.param_shardings(params, mesh)
        bsh = SH.to_shardings(SH.decode_batch_specs(batch, mesh), mesh)
        with mesh:
            lg2 = jax.jit(step)(jax.device_put(params, psh),
                                jax.device_put(batch, bsh))[0]
        np.testing.assert_allclose(np.asarray(lg1),
                                   np.asarray(jax.device_get(lg2)),
                                   rtol=3e-4, atol=3e-4)

    def test_int8_ef_compression_converges():
        """EF-compressed gradient descent reaches the quadratic optimum."""
        rng = np.random.default_rng(0)
        w_true = jnp.asarray(rng.standard_normal(32), jnp.float32)
        x = jnp.zeros(32)
        state = make_ef_state({"w": x})
        for i in range(300):
            g = {"w": 2 * (x - w_true)}
            (gc,), new_state = (lambda t: (jax.tree.leaves(t[0]), t[1]))(
                ef_transform(g, state))
            state = new_state
            x = x - 0.05 * gc
        assert float(jnp.max(jnp.abs(x - w_true))) < 1e-2

    def test_int8_quantize_roundtrip():
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.standard_normal((16, 64)) * 3, jnp.float32)
        c, s = int8_quantize(x)
        y = int8_dequantize(c, s)
        assert float(jnp.max(jnp.abs(x - y))) < float(jnp.max(s)) + 1e-6

    def test_cross_pod_compressed_grads_close_to_exact():
        mesh = _mesh((8,), ("pod",))
        rng = np.random.default_rng(3)
        w = jnp.asarray(rng.standard_normal((16,)), jnp.float32)
        batch = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)

        def loss(params, b):
            return jnp.mean((b @ params) ** 2)

        gfn_c = make_cross_pod_grad_fn(loss, mesh, compress=True)
        gfn_e = make_cross_pod_grad_fn(loss, mesh, compress=False)
        res = jnp.zeros((16,), jnp.float32)
        with mesh:
            gc, _ = gfn_c(w, batch, res)
            ge, _ = gfn_e(w, batch, res)
        rel = float(jnp.linalg.norm(gc - ge) / jnp.linalg.norm(ge))
        assert rel < 0.02, rel

    def test_pipeline_parallel_matches_sequential():
        from repro.training.pipeline import pipeline_apply
        mesh = _mesh((4, 2), ("pod", "model"))
        rng = np.random.default_rng(4)
        S, M, mb, d = 4, 8, 2, 16
        ws = jnp.asarray(rng.standard_normal((S, d, d)) * 0.3, jnp.float32)
        h0 = jnp.asarray(rng.standard_normal((M, mb, d)), jnp.float32)

        def stage_fn(w, h):
            return jnp.tanh(h @ w)

        seq = h0
        for s in range(S):
            seq = stage_fn(ws[s], seq)
        with mesh:
            out = pipeline_apply(stage_fn, ws, h0, mesh,
                                 num_microbatches=M, axis="pod")
        np.testing.assert_allclose(np.asarray(out), np.asarray(seq),
                                   rtol=2e-5, atol=2e-5)

    def test_overlapped_moe_matches_dense():
        from repro.distributed.overlap import overlapped_moe_ffn
        mesh = _mesh((8,), ("model",))
        rng = np.random.default_rng(5)
        n, d, f = 64, 16, 32
        x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
        wu = jnp.asarray(rng.standard_normal((8, d, f)) * 0.2, jnp.float32)
        wd = jnp.asarray(rng.standard_normal((8, f, d)) * 0.2, jnp.float32)
        with mesh:
            y = overlapped_moe_ffn(x, wu.reshape(8 * d, f),
                                   wd.reshape(8 * f, d), mesh,
                                   chunks=2)
        assert y.shape == (n, d)
        assert bool(jnp.isfinite(y).all())
