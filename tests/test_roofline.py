"""Roofline machinery: HLO cost model accuracy + term arithmetic."""
import jax
import jax.numpy as jnp
from jax.sharding import AxisType
import numpy as np
import pytest

from repro.roofline import analysis as RA
from repro.roofline.hlo_cost import analyze


def _compiled(f, *avals):
    return jax.jit(f).lower(*avals).compile()


def test_matmul_matches_xla_cost_analysis():
    f = lambda x, w: jnp.tanh(x @ w)
    c = _compiled(f, jax.ShapeDtypeStruct((256, 512), jnp.float32),
                  jax.ShapeDtypeStruct((512, 512), jnp.float32))
    ours = analyze(c.as_text())
    xla = RA.xla_cost_analysis(c)   # normalizes list-vs-dict across versions
    assert ours["flops"] == pytest.approx(xla["flops"], rel=0.01)
    assert ours["bytes"] == pytest.approx(xla["bytes accessed"], rel=0.05)


def test_scan_trip_count_multiplied():
    def f(x, w):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        c, _ = jax.lax.scan(body, x, w)
        return c
    c = _compiled(f, jax.ShapeDtypeStruct((256, 512), jnp.float32),
                  jax.ShapeDtypeStruct((8, 512, 512), jnp.float32))
    ours = analyze(c.as_text())
    expected = 8 * 2 * 256 * 512 * 512
    assert ours["flops"] == pytest.approx(expected, rel=0.02)
    # weights stream from HBM every iteration
    assert ours["bytes"] >= 8 * 512 * 512 * 4


def test_nested_scan():
    def f(x, w):
        def outer(c, wi):
            def inner(ci, _):
                return jnp.tanh(ci @ wi), None
            ci, _ = jax.lax.scan(inner, c, None, length=4)
            return ci, None
        c, _ = jax.lax.scan(outer, x, w)
        return c
    c = _compiled(f, jax.ShapeDtypeStruct((256, 512), jnp.float32),
                  jax.ShapeDtypeStruct((8, 512, 512), jnp.float32))
    ours = analyze(c.as_text())
    assert ours["flops"] == pytest.approx(32 * 2 * 256 * 512 * 512, rel=0.02)


def test_collective_bytes_on_sharded_program():
    if jax.device_count() < 1:
        pytest.skip("no devices")
    mesh = jax.make_mesh((1,), ("model",), axis_types=(AxisType.Auto,))
    from jax.sharding import NamedSharding, PartitionSpec as P

    def f(x):
        return jax.lax.with_sharding_constraint(
            x @ x.T, NamedSharding(mesh, P(None, None)))
    # single-device: no collectives expected; parse must return zeros
    with mesh:
        c = _compiled(f, jax.ShapeDtypeStruct((64, 64), jnp.float32))
    ours = analyze(c.as_text())
    assert ours["collective_bytes"] == 0


def test_terms_arithmetic():
    t = RA.RooflineTerms(
        arch="x", shape="train_4k", variant="train", mesh="single",
        chips=256, flops_per_device=197e12, bytes_per_device=819e9,
        collective_bytes_per_device=50e9, model_flops=256 * 197e12 / 2)
    assert t.t_compute == pytest.approx(1.0)
    assert t.t_memory == pytest.approx(1.0)
    assert t.t_collective == pytest.approx(1.0)
    assert t.useful_flops_ratio == pytest.approx(0.5)
    assert t.roofline_fraction == pytest.approx(0.5)


def test_model_flops_for():
    from repro.config import SHAPES
    from repro.configs import get_config
    cfg = get_config("yi-6b")
    mf_train = RA.model_flops_for(cfg, SHAPES["train_4k"], "train")
    assert mf_train == pytest.approx(6 * cfg.param_count() * 4096 * 256,
                                     rel=1e-6)
    mf_dec = RA.model_flops_for(cfg, SHAPES["decode_32k"], "decode_thinkv")
    assert mf_dec == pytest.approx(2 * cfg.param_count() * 128, rel=1e-6)
    # MoE uses active params
    moe = get_config("mixtral-8x7b")
    mf = RA.model_flops_for(moe, SHAPES["train_4k"], "train")
    assert mf == pytest.approx(6 * moe.active_param_count() * 4096 * 256,
                               rel=1e-6)
