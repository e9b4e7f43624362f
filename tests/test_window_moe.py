"""Window layers beside ThinKV layers, and held experts, on the served
path: ``ThinKVEngine`` against the plain reference
(``bench/references/window_moe.py``) on a small model with seeded random
weights (4 layers "window, window, window, full", window 32, 4 of 8
experts held, top-2; ``bench/testdata/tiny-window-moe.json``).

One engine (reference backend, 8-bit codes, a budget above every
context, so nothing is evicted) serves the comparisons, the schedule
check and the preemption round trip; the benchmark's own test
(``bench/test_bench_window_moe.py``) runs the kernel backend with
eviction on.
"""
import dataclasses
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import (FULL, WINDOW, ArchFamily, ModelConfig, MoEConfig,
                          RopeConfig, ServeConfig, ThinKVConfig)
from repro.layers import moe as MOE
from repro.models import lm
from repro.serving.engine import ThinKVEngine

BENCH = Path(__file__).resolve().parents[1] / "bench"
CFG = json.loads((BENCH / "testdata" / "tiny-window-moe.json").read_text())
SEED = 5
# no eviction (the budget is above every context) and 8-bit codes: the
# cache is the reference's up to 8-bit rounding and the bf16 buffer/ring
TK = ThinKVConfig(group_size=16, block_size=16, refresh_interval=32,
                  token_budget=512, precision=(8, 8, 8), max_segments=64,
                  kmeans_iters=4)


def _load(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"test_{kind}_{name}", BENCH / kind / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("references", "window_moe")
SYS = _load("systems", "window_moe")
MCFG = SYS.model_config(CFG)


def _engine(**kw):
    return ThinKVEngine(ServeConfig(model=MCFG, thinkv=TK, max_seqs=3,
                                    temperature=0.0),
                        params=REF.init_weights(CFG, SEED),
                        backend="reference", record_logits=True, **kw)


@pytest.fixture(scope="module")
def served():
    """The engine, and request A (a 140-token prompt: one 128-token chunk
    and g-sized ones, then 70 tokens, 210 positions: the rings wrap six
    times) served alone: ``(engine, prompt, output, logits)``."""
    eng = _engine()
    prompt = np.random.default_rng(0).integers(0, CFG["vocab_size"], 140)
    eng.submit([prompt], max_new_tokens=70)
    (req,) = eng.run()
    return eng, prompt, list(req.output), eng.request_logits[req.arrival]


def _ref_logits(prompt, output):
    """Reference logits after each fed token, every position held."""
    m = REF.dims(CFG)
    fed = np.asarray(output[:-1], np.int32)
    P, k = len(prompt), len(fed)
    pad = REF._bucket(P, REF.Q_BLOCK)
    Np = REF._bucket(k, REF.Q_BLOCK)
    pp = np.zeros(pad, np.int32)
    pp[:P] = prompt
    fp = np.zeros(Np, np.int32)
    fp[:k] = fed
    held = np.ones((sum(not w for w in m["window"]), pad + Np), bool)
    w = REF.init_weights(CFG, SEED)
    h = REF._final_hidden_fn(tuple(sorted(m.items())), "f32")(
        w, pp, P, fp, k, held, 0)
    return np.asarray(REF._mm(h[:k], w["embed"]["lm_head"], "f32"))


def test_prefill_then_decode_matches_the_reference(served):
    """Decode logits through the engine agree with the reference's
    attention-late tick over every position.  Tolerance: 0.5% of the
    largest logit, for the 8-bit codes of the full layer, the bf16
    buffer and rings, and the bf16 operands of the window attention
    (measured: 0.0097 against a largest logit of 4.96, 0.19%)."""
    _, prompt, output, logits = served
    assert len(prompt) + len(output) > 6 * CFG["sliding_window"]
    got = np.stack(logits[1:])              # after feeding s_0 .. s_k-1
    want = _ref_logits(prompt, output)
    assert got.shape == want.shape
    tol = 0.005 * np.abs(want).max()
    assert np.abs(got - want).max() < tol, (np.abs(got - want).max(), tol)
    # greedy: every served token is the engine's own argmax
    assert (got.argmax(-1) == np.asarray(output[1:])).all()


def test_prefill_then_decode_with_thinkv_codes_within_a_drift_bound():
    """The same request with the full layer's cache at the benchmark
    configuration's 2/4-bit codes: logits within 4% of the largest
    reference logit, every served token within 0.1 of the reference's
    best (measured: 0.096 against 4.96, 1.9%; widest gap 0.039)."""
    tk = dataclasses.replace(TK, precision=(2, 4, 4))
    eng = ThinKVEngine(ServeConfig(model=MCFG, thinkv=tk, max_seqs=1,
                                   temperature=0.0),
                       params=REF.init_weights(CFG, SEED),
                       backend="reference", record_logits=True)
    prompt = np.random.default_rng(0).integers(0, CFG["vocab_size"], 140)
    eng.submit([prompt], max_new_tokens=70)
    (req,) = eng.run()
    got = np.stack(eng.request_logits[req.arrival][1:])
    want = _ref_logits(prompt, list(req.output))
    assert np.abs(got - want).max() < 0.04 * np.abs(want).max()
    served = np.asarray(req.output[1:])
    gap = want.max(-1) - want[np.arange(len(served)), served]
    assert gap.max() < 0.1, gap.max()


def test_a_slot_does_not_depend_on_its_co_scheduled_slots(served):
    """Dropless held experts (no capacity) and per-slot attention: A's
    logits with two other requests in the batch equal A's alone."""
    eng, prompt, output, logits = served
    rng = np.random.default_rng(1)
    others = [rng.integers(0, CFG["vocab_size"], n) for n in (40, 9)]
    eng.submit([prompt, *others], max_new_tokens=70)
    done = {len(r.prompt): r for r in eng.run()}
    a = done[len(prompt)]
    assert a.output == output
    np.testing.assert_array_equal(np.stack(eng.request_logits[a.arrival]),
                                  np.stack(logits))


def test_mega_dispatch_matches_single_ticks(served):
    """Four ticks fused in one dispatch give A's logits, and the device
    counters summed over the pack's trips."""
    _, prompt, output, logits = served
    mega = _engine(ticks_per_dispatch=4)
    mega.submit([prompt], max_new_tokens=70)
    (req,) = mega.run()
    assert req.output == output
    np.testing.assert_array_equal(np.stack(mega.request_logits[req.arrival]),
                                  np.stack(logits))
    # 69 ticks, every one past the window: 3 rings x 32 positions; 4
    # layers x 4 held experts; at most top-2 pairs a layer on them
    ticks = mega.metrics["ticks"]
    assert ticks == 69 and mega.metrics["dispatches"] < ticks
    assert mega.metrics["window_positions"] == ticks * 3 * 32
    assert mega.metrics["expert_slots"] == ticks * 4 * 4
    assert 0 < mega.metrics["expert_assignments"] <= ticks * 4 * 2
    assert mega.metrics["experts_hit"] <= mega.metrics["expert_assignments"]


def test_preemption_spills_and_restores_the_rings_bit_exactly(served):
    """A is preempted mid-decode (its rings, pool blocks and buffer go to
    the host) and resumed: the spilled rings are the device rings, and
    A's logits and tokens equal the run that was never preempted."""
    eng, prompt, output, logits = served
    eng.submit([prompt], max_new_tokens=70)
    eng.run(max_ticks=45)
    (slot,) = eng.scheduler.active_slots()
    ring = np.asarray(eng.caches.ring_k[slot.idx])
    req = slot.request
    eng._preempt(slot)
    np.testing.assert_array_equal(eng._spilled[req.arrival].cache.ring_k,
                                  ring)
    eng.run()
    assert req.output == output and eng.metrics["resumes"] >= 1
    np.testing.assert_array_equal(np.stack(eng.request_logits[req.arrival]),
                                  np.stack(logits))


def test_prefix_cache_hit_restores_the_rings(served):
    """A prompt that extends a cached prefix maps the prefix's blocks and
    restores its snapshot, rings included: its logits equal those of an
    engine without the cache."""
    rng = np.random.default_rng(2)
    base = rng.integers(0, CFG["vocab_size"], 100)
    ext = np.concatenate([base[:96], rng.integers(0, CFG["vocab_size"],
                                                  14)])
    out = {}
    for eng in (served[0], _engine(prefix_cache=True)):
        for p in (base, ext):
            eng.submit([p], max_new_tokens=24)
            r = max(eng.run(), key=lambda q: q.arrival)
        out[eng.prefix_cache is not None] = np.stack(
            eng.request_logits[r.arrival])
    assert eng.metrics["prefix_hits"] == 1
    np.testing.assert_array_equal(out[True], out[False])


def test_expert_shares_add_up_to_the_whole_layer():
    """The parts two chips holding experts 0-3 and 4-7 compute add up to
    the uncut reference layer (softmax over 8, top-2, renormalised)."""
    E, d, ff = 8, 32, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    w = {"router": jax.random.normal(ks[0], (d, E)) * d ** -0.5,
         "w_gate": jax.random.normal(ks[1], (E, d, ff)) * d ** -0.5,
         "w_up": jax.random.normal(ks[2], (E, d, ff)) * d ** -0.5,
         "w_down": jax.random.normal(ks[3], (E, ff, d)) * ff ** -0.5}
    x = jax.random.normal(ks[4], (24, d))
    base = ModelConfig(name="share", family=ArchFamily.MOE, num_layers=1,
                       d_model=d, num_heads=2, num_kv_heads=1, d_ff=ff,
                       vocab_size=16)
    parts, pairs = [], 0
    for held in ((0, 4), (4, 8)):
        cfg = dataclasses.replace(base, moe=MoEConfig(
            num_experts=E, num_experts_per_token=2, held=held))
        p = dict(w, **{k: w[k][held[0]:held[1]]
                       for k in ("w_gate", "w_up", "w_down")})
        y, assigned, _ = jax.jit(MOE.moe_held, static_argnums=2)(p, x, cfg)
        parts.append(y)
        pairs += int(assigned)
    assert pairs == 24 * 2                  # every routed pair, once
    m = {"E": E, "k": 2, "e0": 0, "held": E, "norm_topk": True}
    with jax.default_matmul_precision("highest"):
        whole = jax.jit(lambda w, x: REF._moe({"moe": w}, x, m, "f32"))(
            w, x)
        np.testing.assert_allclose(np.asarray(parts[0] + parts[1]),
                                   np.asarray(whole), atol=1e-5)


def _model(**kw):
    base = dict(name="refuse", family=ArchFamily.MOE, num_layers=4,
                d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
                d_ff=32, vocab_size=64)
    base.update(kw)
    return ModelConfig(**base)


@pytest.mark.parametrize("case", ["uniform_window", "rope_without_kinds",
                                  "no_full_layer", "window_on_mesh",
                                  "lstar_on_window_layer"])
def test_the_engine_refuses_what_it_does_not_serve(case):
    kinds = (WINDOW, WINDOW, WINDOW, FULL)
    kw, build = {}, {}
    if case == "uniform_window":
        m = _model(sliding_window=16)
    elif case == "rope_without_kinds":
        m = _model(rope_full=RopeConfig(kind="yarn", factor=4.0,
                                        original_max_position=64))
    elif case == "no_full_layer":
        m = _model(sliding_window=16, layer_kinds=(WINDOW,) * 4)
    elif case == "window_on_mesh":
        m = _model(sliding_window=16, layer_kinds=kinds)
        build["mesh"] = jax.make_mesh((1,), ("model",))
    else:
        m = _model(sliding_window=16, layer_kinds=kinds)
        build["lstar"] = (0,)
    err = NotImplementedError if case == "window_on_mesh" else ValueError
    with pytest.raises(err):
        ThinKVEngine(ServeConfig(model=m, thinkv=TK, max_seqs=1, **kw),
                     **build)


def test_paths_without_layer_kinds_or_held_experts_refuse():
    """The FullKV baseline serves one attention kind, and the training
    layer computes every expert: each says so instead of serving
    another model."""
    m = _model(sliding_window=16, layer_kinds=(WINDOW, WINDOW, WINDOW, FULL),
               moe=MoEConfig(num_experts=8, held=(0, 4)))
    with pytest.raises(NotImplementedError):
        lm.prefill({}, {"tokens": jnp.zeros((1, 4), jnp.int32)}, m)
    with pytest.raises(NotImplementedError):
        MOE.moe_apply({}, jnp.zeros((1, 4, 64)), m)
