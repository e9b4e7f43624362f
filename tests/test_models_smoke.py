"""Per-architecture smoke tests: reduced same-family config, one forward +
train step on CPU, asserting output shapes + no NaNs (assignment
requirement), plus decode==forward consistency per family.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ArchFamily
from repro.configs import ARCHS, get_config, get_smoke_config
from repro.models import build_model, encdec, hybrid, lm, ssm_lm
from repro.training.optimizer import adamw_init, adamw_update
from repro.config import OptimizerConfig


def _batch(cfg, rng, b=2, s=32):
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)),
                              jnp.int32),
        "targets": jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)),
                               jnp.int32),
    }
    if cfg.family == ArchFamily.VLM:
        batch["patches"] = jnp.asarray(
            rng.standard_normal((b, cfg.num_image_tokens, cfg.frontend_dim)),
            jnp.float32)
    if cfg.family == ArchFamily.ENCDEC:
        batch["frames"] = jnp.asarray(
            rng.standard_normal((b, cfg.encoder_seq, cfg.d_model)),
            jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_and_train_step(arch, rng):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init_params(0)
    batch = _batch(cfg, rng)

    lg, aux = jax.jit(lambda p, b: model.logits(p, b, cfg))(params, batch)
    exp_s = 32 + (cfg.num_image_tokens if cfg.family == ArchFamily.VLM
                  else 0)
    assert lg.shape == (2, exp_s, cfg.vocab_size)
    assert bool(jnp.isfinite(lg).all()), f"{arch}: NaN/inf logits"

    # one full train step (loss + grad + AdamW)
    def loss(p):
        return model.loss(p, batch, cfg, remat=True)[0]
    l0, grads = jax.jit(jax.value_and_grad(loss))(params)
    assert bool(jnp.isfinite(l0)), arch
    opt = adamw_init(params)
    new_params, opt, m = adamw_update(OptimizerConfig(), grads, opt, params)
    assert bool(jnp.isfinite(m["grad_norm"]))
    changed = any(
        not np.allclose(np.asarray(a, np.float32), np.asarray(b, np.float32))
        for a, b in zip(jax.tree.leaves(params),
                        jax.tree.leaves(new_params)))
    assert changed, f"{arch}: params did not update"


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_matches_assignment(arch):
    """The full (dry-run) configs carry the exact assigned dimensions."""
    cfg = get_config(arch)
    table = {
        "yi-6b": (32, 4096, 32, 4, 11008, 64000),
        "yi-9b": (48, 4096, 32, 4, 11008, 64000),
        "qwen2-7b": (28, 3584, 28, 4, 18944, 152064),
        "mistral-large-123b": (88, 12288, 96, 8, 28672, 32768),
        "mixtral-8x7b": (32, 4096, 32, 8, 14336, 32000),
        "llama4-scout-17b-a16e": (48, 5120, 40, 8, 8192, 202048),
        "paligemma-3b": (18, 2048, 8, 1, 16384, 257216),
        "whisper-medium": (24, 1024, 16, 16, 4096, 51865),
        "falcon-mamba-7b": (64, 4096, 0, 0, 0, 65024),
        "zamba2-7b": (81, 3584, 32, 32, 14336, 32000),
        "r1-llama-8b": (32, 4096, 32, 8, 14336, 128256),
        "mellum2-12b-a2.5b": (28, 2304, 32, 4, 896, 98304),
    }
    L, d, h, kv, ff, v = table[arch]
    assert cfg.num_layers == L and cfg.d_model == d
    assert cfg.num_heads == h and cfg.num_kv_heads == kv
    assert cfg.d_ff == ff and cfg.vocab_size == v
    if arch == "mixtral-8x7b":
        assert cfg.moe.num_experts == 8
        assert cfg.moe.num_experts_per_token == 2
    if arch == "llama4-scout-17b-a16e":
        assert cfg.moe.num_experts == 16
        assert cfg.moe.num_experts_per_token == 1
    if arch == "falcon-mamba-7b":
        assert cfg.ssm.state_size == 16
    if arch == "zamba2-7b":
        assert cfg.ssm.state_size == 64
    if arch == "qwen2-7b":
        assert cfg.qkv_bias
    if arch == "mellum2-12b-a2.5b":
        assert cfg.moe.num_experts == 64 and cfg.moe.num_held == 64
        assert cfg.moe.num_experts_per_token == 8
        assert cfg.sliding_window == 1024 and cfg.qk_norm
        assert cfg.layer_kinds[:4] == ("window",) * 3 + ("full",) \
            and len(cfg.window_layers) == 21


def test_dense_decode_matches_forward(rng):
    cfg = get_smoke_config("yi-6b")
    model = build_model(cfg)
    params = model.init_params(2)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 8)), jnp.int32)
    lg_full, _ = model.logits(params, {"tokens": toks}, cfg)
    kc = jnp.zeros((cfg.num_layers, 16, cfg.num_kv_heads, cfg.head_dim),
                   jnp.float32)
    vc = jnp.zeros_like(kc)
    for i in range(8):
        lg, kc, vc = lm.decode_step_fullkv(params, toks[0, i], jnp.int32(i),
                                           kc, vc, jnp.int32(i), cfg)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(lg_full[0, -1]),
                               rtol=2e-3, atol=2e-3)


def test_moe_decode_matches_forward_dropless(rng):
    """With a no-drop capacity factor decode == teacher-forced forward
    (capacity dropping is the only train/decode divergence)."""
    cfg = get_smoke_config("mixtral-8x7b")
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    model = build_model(cfg)
    params = model.init_params(2)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 8)), jnp.int32)
    lg_full, _ = model.logits(params, {"tokens": toks}, cfg)
    kc = jnp.zeros((cfg.num_layers, 16, cfg.num_kv_heads, cfg.head_dim),
                   jnp.float32)
    vc = jnp.zeros_like(kc)
    for i in range(8):
        lg, kc, vc = lm.decode_step_fullkv(params, toks[0, i], jnp.int32(i),
                                           kc, vc, jnp.int32(i), cfg)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(lg_full[0, -1]),
                               rtol=2e-3, atol=2e-3)


def test_ssm_decode_matches_forward(rng):
    cfg = get_smoke_config("falcon-mamba-7b")
    model = build_model(cfg)
    params = model.init_params(1)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 12)), jnp.int32)
    lg_full, _ = model.logits(params, {"tokens": toks}, cfg)
    st = ssm_lm.init_decode_state(cfg)
    for i in range(12):
        lg, st = ssm_lm.decode_step(params, toks[0, i], st, cfg)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(lg_full[0, -1]),
                               rtol=5e-3, atol=5e-3)


def test_hybrid_decode_matches_forward(rng):
    cfg = get_smoke_config("zamba2-7b")
    model = build_model(cfg)
    params = model.init_params(1)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 12)), jnp.int32)
    lg_full, _ = model.logits(params, {"tokens": toks}, cfg)
    st = hybrid.init_decode_state(cfg)
    na = cfg.num_attention_layers()
    kc = jnp.zeros((na, 16, cfg.num_kv_heads, cfg.head_dim), jnp.float32)
    vc = jnp.zeros_like(kc)
    for i in range(12):
        lg, st, kc, vc = hybrid.decode_step_fullkv(
            params, toks[0, i], jnp.int32(i), st, kc, vc, jnp.int32(i), cfg)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(lg_full[0, -1]),
                               rtol=5e-3, atol=5e-3)


def test_whisper_decode_matches_forward(rng):
    cfg = get_smoke_config("whisper-medium")
    model = build_model(cfg)
    params = model.init_params(2)
    frames = jnp.asarray(rng.standard_normal((1, cfg.encoder_seq,
                                              cfg.d_model)), jnp.float32)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 8)), jnp.int32)
    lg_full, _ = model.logits(params, {"tokens": toks, "frames": frames},
                              cfg)
    enc = encdec.encode(params, frames, cfg)
    ck, cv = encdec.cross_caches(params, enc, cfg)
    kc = jnp.zeros((cfg.num_layers, 16, cfg.num_kv_heads, cfg.head_dim),
                   jnp.float32)
    vc = jnp.zeros_like(kc)
    for i in range(8):
        lg, kc, vc = encdec.decode_step_fullkv(
            params, toks[0, i], jnp.int32(i), kc, vc, jnp.int32(i),
            ck[:, 0], cv[:, 0], cfg)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(lg_full[0, -1]),
                               rtol=2e-3, atol=2e-3)
