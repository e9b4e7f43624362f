"""End-to-end system behaviour: the paper's pipeline on synthetic reasoning
traces — calibration -> thought classification -> TBQ/TBE/CT serving —
validated against the paper's own qualitative claims.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ServeConfig, ThinKVConfig, ThoughtType
from repro.configs import get_smoke_config
from repro.core import calibration as CAL
from repro.core import ct_cache as CC
from repro.core import thinkv as TV
from repro.data.synthetic import ReasoningTraceGen
from repro.serving.engine import ThinKVEngine


def test_calibrate_then_serve_pipeline(rng):
    """Offline calibration feeds the online classifier; a full generation
    under the resulting config keeps the budget and shows thought-adaptive
    precision (paper Secs. 4.1-4.3 composed)."""
    gen = ReasoningTraceGen(dataset="aime", seg_len_range=(50, 120), seed=0)
    res = CAL.calibrate(gen.calibration_traces(4, 2000, 8, lstar=[1, 3, 5, 6]),
                        num_thoughts=3, num_calib_layers=4)
    tk = ThinKVConfig(refresh_interval=16, group_size=8, block_size=8,
                      token_budget=64, retention_schedule=(16, 8, 4),
                      min_retention=4, max_segments=128, kmeans_iters=4,
                      sparsity_thresholds=tuple(res.thresholds))
    dims = CC.make_dims(tk, num_layers=2, kv_heads=2, head_dim=32)
    cache = CC.init_cache(dims)
    view = CC.init_pool_view(dims)
    step = jax.jit(functools.partial(TV.step_token, tk, dims))
    trace = gen.generate(600)
    for i in range(600):
        k = jnp.asarray(rng.standard_normal((2, 2, 32)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((2, 2, 32)), jnp.float32)
        cache, view = step(cache, view, k, v, jnp.float32(trace.sparsities[i]))

    counts = np.asarray(CC.valid_counts(cache))
    floor = tk.min_retention * int(cache.cur_seg) + tk.refresh_interval
    assert (counts <= max(tk.token_budget, floor) + dims.G).all()

    # classified segment types should track the planted ones
    n_seg = int(cache.cur_seg)
    seg_types = np.asarray(cache.seg_type[:n_seg])
    planted = trace.thought_types
    matches = total = 0
    for s in range(1, n_seg):
        lo, hi = s * 16, min((s + 1) * 16, 600)
        if lo >= 600:
            break
        true = np.bincount(planted[lo:hi], minlength=3).argmax()
        matches += int(seg_types[s] == true)
        total += 1
    assert matches / total > 0.8, (matches, total)

    comp = TV.compression_ratio(tk, dims, cache, jnp.int32(600))
    assert float(comp["footprint_frac"]) < 0.30
    assert 2.0 < float(comp["avg_bits"]) < 4.0   # T tokens present


def test_transition_outliers_not_fully_evicted(rng):
    """Paper Sec. 6.3 / Fig. 11(a): min retention keeps >=4 tokens of every
    annealed segment — transitions are never fully dropped (full eviction
    causes endless reasoning loops, App. E.17)."""
    tk = ThinKVConfig(refresh_interval=16, group_size=8, block_size=8,
                      token_budget=48, retention_schedule=(8, 4),
                      min_retention=4, max_segments=64, kmeans_iters=4)
    dims = CC.make_dims(tk, num_layers=1, kv_heads=2, head_dim=32)
    cache = CC.init_cache(dims)
    view = CC.init_pool_view(dims)
    step = jax.jit(functools.partial(TV.step_token, tk, dims))
    spars = [0.9, 0.65, 0.9, 0.3, 0.9, 0.65]   # transition-heavy
    for i in range(400):
        k = jnp.asarray(rng.standard_normal((1, 2, 32)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((1, 2, 32)), jnp.float32)
        cache, view = step(cache, view, k, v, jnp.float32(spars[(i // 16) % 6]))
    seg = np.asarray(cache.slot_seg[0])
    stt = np.asarray(cache.slot_state[0])
    seg_types = np.asarray(cache.seg_type)
    kept = []
    for s in range(int(cache.cur_seg)):
        if seg_types[s] == int(ThoughtType.TRANSITION):
            kept.append(int(((seg == s) & (stt == 1)).sum()))
    survivors = [c for c in kept if c > 0]
    assert survivors, "all transition segments vanished"
    assert np.mean([c >= tk.min_retention for c in survivors]) > 0.5


def test_proactive_vs_per_step_eviction_rates(rng):
    """Paper Table 5: ThinKV evicts in ~4.6% of decode steps (proactive,
    segment-level) vs per-token baselines' ~83%.  Count eviction events."""
    tk = ThinKVConfig(refresh_interval=16, group_size=8, block_size=8,
                      token_budget=64, retention_schedule=(16, 8, 4),
                      min_retention=4, max_segments=64, kmeans_iters=4)
    dims = CC.make_dims(tk, num_layers=1, kv_heads=2, head_dim=32)
    cache = CC.init_cache(dims)
    view = CC.init_pool_view(dims)
    step = jax.jit(functools.partial(TV.step_token, tk, dims))
    spars = [0.65, 0.3, 0.9, 0.65]
    evict_steps = 0
    n = 400
    prev_evicted = 0
    for i in range(n):
        k = jnp.asarray(rng.standard_normal((1, 2, 32)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((1, 2, 32)), jnp.float32)
        cache, view = step(cache, view, k, v, jnp.float32(spars[(i // 16) % 4]))
        total_committed = (i + 1) - int(cache.buf_len)
        valid = int(np.asarray(CC.valid_counts(cache)[0]))
        evicted_so_far = total_committed - valid
        if evicted_so_far > prev_evicted:
            evict_steps += 1
        prev_evicted = evicted_so_far
    rate = evict_steps / n
    assert rate < 0.15, rate


def test_engine_with_moe_backbone(rng):
    # the engine serves a window only as window layers (layer_kinds) and
    # refuses mixtral's uniform sliding_window; its full-attention form
    cfg = dataclasses.replace(get_smoke_config("mixtral-8x7b"),
                              sliding_window=0)
    tk = ThinKVConfig(refresh_interval=16, group_size=8, block_size=8,
                      token_budget=48, retention_schedule=(16, 8, 4),
                      min_retention=4, max_segments=64, kmeans_iters=4)
    eng = ThinKVEngine(ServeConfig(model=cfg, thinkv=tk, max_seqs=2,
                                   temperature=0.0))
    eng.submit([rng.integers(0, cfg.vocab_size, 6) for _ in range(2)],
               max_new_tokens=20)
    done = eng.run()
    assert len(done) == 2 and all(len(r.output) == 20 for r in done)


def test_engine_with_vlm_backbone(rng):
    cfg = get_smoke_config("paligemma-3b")
    tk = ThinKVConfig(refresh_interval=16, group_size=8, block_size=8,
                      token_budget=48, retention_schedule=(16, 8, 4),
                      min_retention=4, max_segments=64, kmeans_iters=4)
    eng = ThinKVEngine(ServeConfig(model=cfg, thinkv=tk, max_seqs=2,
                                   temperature=0.0))
    eng.submit([rng.integers(0, cfg.vocab_size, 6)], max_new_tokens=12)
    done = eng.run()
    assert len(done) == 1 and len(done[0].output) == 12
