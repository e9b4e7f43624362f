"""Multi-tick decode mega-dispatch + COW-forked generation.

Pins the tentpole contracts of the fused-N-ticks dispatch:

* greedy outputs with ``ticks_per_dispatch=N`` are BIT-IDENTICAL to the
  N=1 path (the tick core is shared; only dispatch granularity changes),
  at temperature>0 too (per-request sampling streams are
  schedule-invariant);
* Python dispatches per decoded token drop measurably below 1;
* the loop exits early at scheduling events — a slot finishing mid-pack
  (``early_exit_finish``) and commit-claim headroom exhaustion
  (``early_exit_headroom``, trips capped by ``_safe_decode_trips``);
* packed :class:`MultiResultTokens` semantics: per-trip validity masks,
  rows past the executed trip count zero/ignored;
* ``while``-aware launch auditing: exactly one fused pallas launch per
  TRIP on the kernel backend, zero launches outside the loop;
* COW forks (``fork_slot``): shared-prefix refcounts exceed 1, shared
  block content is never written in place (divergence goes through COW
  faults), and at temperature 0 a fork emits exactly its parent's
  tokens.
"""
import dataclasses

import numpy as np

from repro.config import ServeConfig, ThinKVConfig
from repro.configs import get_smoke_config
from repro.serving.engine import MultiResultTokens, ResultTokens, \
    ThinKVEngine

TK = ThinKVConfig(refresh_interval=16, group_size=8, block_size=8,
                  token_budget=48, retention_schedule=(16, 8, 4),
                  min_retention=4, max_segments=64, kmeans_iters=4)


def _cfg(slots=3, temperature=0.0, **tk_over):
    tk = dataclasses.replace(TK, **tk_over)
    return ServeConfig(model=get_smoke_config("r1-llama-8b"), thinkv=tk,
                       max_seqs=slots, temperature=temperature)


def _prompts(rng, n, lo=6, hi=14):
    cfg = get_smoke_config("r1-llama-8b")
    return [rng.integers(0, cfg.vocab_size, rng.integers(lo, hi))
            for _ in range(n)]


def _outputs(done):
    return {r.uid: r.output for r in done}


def test_mega_dispatch_greedy_parity_and_dispatch_amortization(rng):
    """Acceptance: N=8 mega-dispatch emits bit-identical greedy tokens to
    the N=1 path, with dispatches/token measurably < 1."""
    cfg = _cfg()
    prompts = _prompts(rng, 4)
    eng1 = ThinKVEngine(cfg, backend="reference")
    eng1.submit([p.copy() for p in prompts], max_new_tokens=24)
    out1 = _outputs(eng1.run())

    eng8 = ThinKVEngine(cfg, params=eng1.params, backend="reference",
                       ticks_per_dispatch=8)
    eng8.submit([p.copy() for p in prompts], max_new_tokens=24)
    out8 = _outputs(eng8.run())

    assert out1 == out8
    eng1.audit_pool(), eng8.audit_pool()
    # every decoded token used to cost >= 1 Python dispatch; now a pack
    # of up to 8 ticks costs one
    assert eng8.metrics["ticks"] == eng1.metrics["ticks"]
    assert eng8.metrics["dispatches"] < eng8.metrics["ticks"]
    decoded = eng8.metrics["tokens"]
    assert eng8.metrics["dispatches"] / decoded < 1.0
    assert eng8.metrics["ticks"] / eng8.metrics["dispatches"] > 1.0
    # a pack sums the mapped pages its trips read, as single ticks do
    for key in ("attn_pages", "attn_page_slots"):
        assert eng8.metrics[key] == eng1.metrics[key] > 0, key


def test_mega_dispatch_temperature_parity(rng):
    """Schedule invariance at temperature>0: per-request sampling streams
    make the SAMPLED token sequence identical between dispatch
    granularities, not just the greedy one."""
    cfg = _cfg(temperature=0.7)
    cfg = dataclasses.replace(cfg, top_p=0.9)
    prompts = _prompts(rng, 3)
    eng1 = ThinKVEngine(cfg, backend="reference")
    eng1.submit([p.copy() for p in prompts], max_new_tokens=16)
    out1 = _outputs(eng1.run())
    eng4 = ThinKVEngine(cfg, params=eng1.params, backend="reference",
                        ticks_per_dispatch=4)
    eng4.submit([p.copy() for p in prompts], max_new_tokens=16)
    out4 = _outputs(eng4.run())
    assert out1 == out4
    # non-degenerate: temperature actually sampled off-argmax somewhere
    greedy = ThinKVEngine(dataclasses.replace(cfg, temperature=0.0),
                          params=eng1.params, backend="reference")
    greedy.submit([p.copy() for p in prompts], max_new_tokens=16)
    outg = _outputs(greedy.run())
    assert outg != out1


def test_early_exit_on_finish_and_packed_validity(rng):
    """A slot reaching max_new_tokens mid-pack stops the loop after that
    trip (early_exit_finish) and its later-trip rows are invalid."""
    cfg = _cfg(slots=2)
    prompts = _prompts(rng, 2)
    eng = ThinKVEngine(cfg, backend="reference", ticks_per_dispatch=8)
    # max_new 12: prefill emits token 1, ticks emit 11 more -> the pack
    # boundary cannot align with 8-trip packs, so some pack must exit
    # early on the finish event
    eng.submit([p.copy() for p in prompts], max_new_tokens=12)
    done = eng.run()
    assert len(done) == 2
    assert eng.metrics["early_exit_finish"] >= 1
    assert all(len(r.output) == 12 for r in done)


def test_packed_result_semantics_direct(rng):
    """Drive generate/consume by hand: the packed result type, trip
    count, per-trip validity, and zeroed rows past the executed trips."""
    cfg = _cfg(slots=1)
    eng = ThinKVEngine(cfg, backend="reference", ticks_per_dispatch=4)
    eng.submit(_prompts(rng, 1), max_new_tokens=3)   # prefill + 2 ticks
    from repro.serving.orchestrator import Orchestrator
    import jax
    orch = Orchestrator(eng)
    import asyncio

    async def one_pack():
        await orch._admit_and_prefill()
        res, _ = eng.generate(jax.random.PRNGKey(0))
        return res

    res = asyncio.run(one_pack())
    assert isinstance(res, MultiResultTokens) and res.packed
    eng.consume(res)
    assert res.requested == 4
    assert res.trips_host == 2                  # exits when slot finishes
    assert res.valid_host[:2, 0].all()
    assert not res.valid_host[2:].any()         # rows past trips are dead
    assert (res.tokens_host[2:] == 0).all()
    assert eng.metrics["ticks"] == 2
    assert eng.metrics["early_exit_finish"] == 1


def test_single_tick_mode_returns_unpacked_result(rng):
    cfg = _cfg(slots=1)
    eng = ThinKVEngine(cfg, backend="reference")
    assert eng._megatick is None
    eng.submit(_prompts(rng, 1), max_new_tokens=4)
    done = eng.run()
    assert len(done) == 1
    assert not ResultTokens.packed


def test_safe_trips_shrink_under_pool_pressure(rng):
    """A pool sized for ~one commit caps the precomputed trip count below
    ticks_per_dispatch (early_exit_headroom) — yet every token is still
    served without drops."""
    cfg = _cfg(slots=2, token_budget=32)
    prompts = _prompts(rng, 2, lo=8, hi=9)
    probe = ThinKVEngine(cfg, backend="reference")
    pool_blocks = max(2 * (32 + TK.group_size) // TK.block_size, 8)
    eng = ThinKVEngine(cfg, params=probe.params, backend="reference",
                       ticks_per_dispatch=8, pool_blocks=pool_blocks)
    eng.submit([p.copy() for p in prompts], max_new_tokens=40)
    done = eng.run()
    assert len(done) == 2 and all(len(r.output) == 40 for r in done)
    assert eng.metrics["early_exit_headroom"] >= 1
    eng.audit_pool()


def test_megatick_while_aware_launch_audit(rng):
    """CI gate inside the loop: the kernel-backend mega-dispatch stages
    exactly ONE fused pallas launch PER TRIP and none outside the while
    loop; the reference backend stages zero anywhere.  The full contract
    audit (repro.analysis) pins the same counts plus the collective /
    callback / fp64 / branch-divergence rules on every entry point."""
    cfg = _cfg(slots=2)
    ref = ThinKVEngine(cfg, backend="reference", ticks_per_dispatch=2)
    ker = ThinKVEngine(cfg, params=ref.params, backend="kernel",
                       ticks_per_dispatch=2)
    ref.audit_compiled().raise_on_violation()
    rep = ker.audit_compiled().raise_on_violation()
    assert ref.megatick_launch_count() == (0, 0)
    per_trip, outside = ker.megatick_launch_count()
    assert per_trip == ker.tick_launch_count() == 1
    assert outside == 0
    mega = rep.entries["_megatick_fn"].census
    assert (mega.launches_per_trip, mega.launches) == (per_trip, outside)


def test_fork_slot_shares_blocks_and_emits_parent_tokens(rng):
    """fork_slot increfs every parent block (refcount > 1, zero copies),
    never writes shared content in place, and a greedy fork emits its
    parent's exact tokens."""
    cfg = _cfg(slots=2)
    eng = ThinKVEngine(cfg, backend="reference", allow_forks=True)
    import asyncio

    from repro.serving.orchestrator import Orchestrator
    orch = Orchestrator(eng)
    prompt = rng.integers(0, 256, 24)

    async def go():
        # max_new 64 >> budget 48: TBE eviction frees slots INSIDE the
        # shared prompt blocks and later commits reuse them — the write
        # that must COW-fault while the fork still shares the block
        stream = orch.submit(prompt, max_new_tokens=64,
                             samples_per_slot=2)
        orch.close()
        done = await orch.serve()
        return stream, done

    stream, done = asyncio.run(go())
    assert len(done) == 2
    assert eng.metrics["forks"] == 1
    assert eng.metrics["peak_refcount"] > 1       # shared-prefix blocks
    child = stream.forks[0].request
    assert child.output == stream.request.output  # greedy fork parity
    # divergence is paid through COW faults on the forked slots, never
    # in-place writes to shared blocks
    assert eng.metrics["fork_cow_faults"] >= 1
    eng.audit_pool()


def test_fork_shared_blocks_are_immutable(rng):
    """Direct check of the zero-writes-to-shared-blocks claim: snapshot
    every shared physical block's planes at fork time; after further
    decode packs, any block STILL shared holds bit-identical planes
    (writers COW-faulted away instead of dirtying the shared copy)."""
    import asyncio

    import jax

    cfg = _cfg(slots=2, token_budget=32)
    eng = ThinKVEngine(cfg, backend="reference", ticks_per_dispatch=4,
                       allow_forks=True)
    from repro.serving.orchestrator import Orchestrator
    orch = Orchestrator(eng)
    prompt = _prompts(rng, 1, lo=16, hi=17)[0]

    async def fork_then_snapshot():
        stream = orch.submit(prompt, max_new_tokens=40,
                             samples_per_slot=2)
        orch.close()
        orch._rng = jax.random.PRNGKey(eng.cfg.seed)
        await orch._admit_and_prefill()          # prefill parent
        res, orch._rng = eng.generate(orch._rng)  # parent decodes a pack
        eng.consume(res)
        await orch._admit_and_prefill()          # fork lands here
        assert eng.metrics["forks"] == 1
        rc0 = np.asarray(eng.pool.refcount)
        shared0 = rc0 > 1
        assert shared0.any()
        planes0 = [np.asarray(p).copy() for p in eng.pool.view]
        for _ in range(4):                        # both sides diverge
            res, orch._rng = eng.generate(orch._rng)
            eng.consume(res)
        rc1 = np.asarray(eng.pool.refcount)
        still = shared0 & (rc1 > 1)
        assert still.any()
        for p0, p1 in zip(planes0, eng.pool.view):
            p1 = np.asarray(p1)
            for l in range(still.shape[0]):
                assert (p0[l][still[l]] == p1[l][still[l]]).all(), \
                    "shared block planes were written in place"

    asyncio.run(fork_then_snapshot())
