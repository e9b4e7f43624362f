"""Compiled-path contract auditor acceptance tests (docs/analysis.md).

* property test: the walker's static launch counts match RUNTIME-observed
  launch counts on randomized scan/while/cond nests (a pallas "counter"
  kernel increments an accumulator once per executed launch);
* per-branch cond counts: divergent branches are reported and rejected —
  the legacy max-over-branches shim would have hidden them;
* collective census + whitelist: the float-psum-across-shards violation
  is named with its primitive, dtype, and jaxpr path;
* deliberate violations fail loudly (extra launch, float collective,
  steady-state retrace);
* engine audits pass on both backends, and a full streamed pressure
  trace replays with ZERO steady-state retraces under the RetraceGuard;
* the AST lint rules catch their fixture violations and pass the repo.
"""
import importlib.util
from pathlib import Path

import jax
import jax.experimental.pallas as pl
import jax.numpy as jnp
import numpy as np
import pytest
from _prop import given, settings, strategies as st

from repro.analysis import (CompiledContract, RetraceGuard,
                            RetraceViolation, audit_engine, census_of,
                            serve_collective_rule)
from repro.config import ServeConfig, ThinKVConfig
from repro.configs import get_smoke_config
from repro.kernels import ops
from repro.serving.engine import ThinKVEngine

TK = ThinKVConfig(refresh_interval=16, group_size=8, block_size=8,
                  token_budget=48, retention_schedule=(16, 8, 4),
                  min_retention=4, max_segments=64, kmeans_iters=4)


def _engine(backend, params=None, **kw):
    scfg = ServeConfig(model=get_smoke_config("r1-llama-8b"), thinkv=TK,
                       max_seqs=3, temperature=0.0)
    return ThinKVEngine(scfg, params=params, backend=backend, **kw)


# ---------------------------------------------------------------------------
# a runtime-observable launch: one pallas kernel that increments its
# input, threaded as an accumulator through randomized control flow —
# the final value IS the number of launches that actually executed
# ---------------------------------------------------------------------------

def _inc_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] + 1.0


def _launch(x):
    return pl.pallas_call(
        _inc_kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=True)(x)


TRIPS = 2          # every generated while_loop runs exactly this many


def _gen(rng, depth):
    """Random scan/while/cond nest -> (fn: x -> x, model(T) -> launches).

    ``model`` is an independent python-side count of launches executed
    when every while runs T trips — the ground truth both the census and
    the runtime accumulator are checked against.  cond branches are
    generated launch-count-EQUAL here (runtime takes one branch, so a
    divergent pair could not match both); divergence is covered by its
    own test below."""
    r = rng.random()
    if depth == 0 or r < 0.3:
        return _launch, lambda T: 1
    if r < 0.5:
        a, ca = _gen(rng, depth - 1)
        b, cb = _gen(rng, depth - 1)
        return (lambda x: b(a(x))), (lambda T: ca(T) + cb(T))
    if r < 0.7:
        n = int(rng.integers(1, 4))
        sub, cs = _gen(rng, depth - 1)

        def f_scan(x, sub=sub, n=n):
            y, _ = jax.lax.scan(lambda c, _: (sub(c), None), x, None,
                                length=n)
            return y
        return f_scan, lambda T: n * cs(T)
    if r < 0.85:
        sub, cs = _gen(rng, depth - 1)

        def f_while(x, sub=sub):
            def body(c):
                i, y = c
                return i + 1, sub(y)
            _, y = jax.lax.while_loop(lambda c: c[0] < TRIPS, body,
                                      (jnp.int32(0), x))
            return y
        return f_while, lambda T: T * cs(T)
    sub, cs = _gen(rng, depth - 1)
    flag = bool(rng.integers(0, 2))

    def f_cond(x, sub=sub, flag=flag):
        return jax.lax.cond(jnp.bool_(flag), sub,
                            lambda y: sub(y + 0.0), x)
    return f_cond, cs


@settings(max_examples=10, deadline=None)
@given(st.integers(min_value=0, max_value=10**6))
def test_census_launch_count_matches_runtime(seed):
    """Static census launch count == python model == launches actually
    executed, on randomized scan/while/cond nests; the compat shim in
    kernels.ops agrees (branches are equal-count here)."""
    rng = np.random.default_rng(seed)
    fn, model = _gen(rng, depth=3)
    x = jnp.zeros(2, jnp.float32)
    jaxpr = jax.make_jaxpr(fn)(x)
    census = census_of(jaxpr)
    static = census.launches_at(TRIPS)
    runtime = int(np.asarray(fn(x))[0])
    assert static == model(TRIPS) == runtime, (
        static, model(TRIPS), runtime)
    assert ops.count_pallas_launches(jaxpr, while_trips=TRIPS) == static


def test_divergent_cond_branches_reported_and_rejected():
    """Per-branch launch counts are recorded, divergence is flagged as a
    contract violation with the cond's path named — while the legacy
    shim still reports only the max (the bug the walker fixes)."""
    def fn(x):
        return jax.lax.cond(x[0] > 0,
                            lambda y: _launch(_launch(y)), _launch, x)

    jaxpr = jax.make_jaxpr(fn)(jnp.zeros(2, jnp.float32))
    census = census_of(jaxpr)
    assert len(census.cond_launches) == 1
    # branch ORDER in the jaxpr is an implementation detail; the counts
    # and the divergence flag are the contract surface
    assert sorted(census.cond_launches[0].branches) == [1, 2]
    assert census.cond_launches[0].divergent
    v = CompiledContract("t", launches=2).check(census)
    bad = [x for x in v if x.rule == "branch-divergence"]
    assert len(bad) == 1 and "cond" in bad[0].path
    assert "branch" in bad[0].message
    # legacy shim: max over branches (documented compat caveat)
    assert ops.count_pallas_launches(jaxpr) == 2


def test_extra_launch_fails_loudly():
    """A deliberate extra launch against a launches=1 contract produces
    a violation naming the count and the pallas launch sites."""
    fn = lambda x: _launch(_launch(x))                          # noqa: E731
    census = census_of(jax.make_jaxpr(fn)(jnp.zeros(2, jnp.float32)))
    v = CompiledContract("tick", launches=1).check(census)
    assert len(v) == 1 and v[0].rule == "launch-count"
    assert "2 pallas launch" in v[0].message
    assert "pallas_call" in v[0].message          # the offending sites


def test_collective_census_and_float_psum_violation():
    """The census records every collective with dtype + axis; the serve
    whitelist passes the tiled all_gather and the integer psum, and
    rejects a float psum naming primitive, dtype, and shard_map path."""
    from jax.sharding import AxisType, Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:1]), ("model",),
                axis_types=(AxisType.Auto,))

    def body(x, m):
        g = jax.lax.all_gather(x, "model", axis=0, tiled=True)
        dirty = jax.lax.psum(m, "model")                 # int OR: allowed
        bad = jax.lax.psum(x, "model")                   # float: forbidden
        return g + bad, dirty

    f = jax.shard_map(body, mesh=mesh, in_specs=(P("model"), P()),
                      out_specs=(P(), P()), check_vma=False)
    census = census_of(jax.make_jaxpr(f)(
        jnp.ones(4, jnp.float32), jnp.ones((), jnp.int32)))
    got = {(c.name, c.dtype) for c in census.collectives}
    assert {("all_gather", "float32"), ("psum", "int32"),
            ("psum", "float32")} <= got
    assert all(c.axis_names == ("model",) for c in census.collectives)
    v = serve_collective_rule().check("tick", census.collectives)
    assert len(v) == 1, v
    assert "psum(float32)" in v[0].message and "shard_map" in v[0].path


def test_callback_census_and_violation():
    """Host callbacks land in the census with their jaxpr path and
    violate the default contract."""
    def fn(x):
        return jax.pure_callback(
            lambda a: np.asarray(a), jax.ShapeDtypeStruct(x.shape,
                                                          x.dtype), x)
    census = census_of(jax.make_jaxpr(fn)(jnp.zeros(2, jnp.float32)))
    assert len(census.callbacks) == 1
    v = CompiledContract("t").check(census)
    assert any(x.rule == "callback" for x in v)


# ---------------------------------------------------------------------------
# engine audits
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engines():
    ref = _engine("reference", ticks_per_dispatch=4)
    ker = _engine("kernel", params=ref.params, ticks_per_dispatch=4)
    return ref, ker


def test_audit_engine_passes_both_backends(engines):
    """Every registered entry point has a declared contract and passes:
    kernel = {tick: 1, megatick: 1/trip, prefill: L, big: 2L}, reference
    = zero launches everywhere."""
    ref, ker = engines
    L = ker.dims.L
    for eng, tick in ((ref, 0), (ker, 1)):
        rep = audit_engine(eng)
        assert rep.ok, rep.summary()
        assert set(rep.entries) == {"_tick_fn", "_megatick_fn",
                                    "_prefill_chunk_fn",
                                    "_prefill_big_fn"}
        e = rep.entries
        assert e["_tick_fn"].census.launches_at(1) == tick
        assert e["_megatick_fn"].census.launches_per_trip == tick
        assert e["_megatick_fn"].census.launches == 0
        assert e["_prefill_chunk_fn"].census.launches == tick * L
        assert e["_prefill_big_fn"].census.launches == tick * 2 * L
        assert rep.meta["backend"] == eng.backend


def test_unregistered_entry_point_is_an_error(engines):
    """audit_engine refuses an entry point with no declared contract —
    new compiled paths must declare their invariants."""
    ref, _ = engines
    orig = ref.compiled_entry_points

    def with_rogue():
        eps = orig()
        eps["_rogue_fn"] = eps["_tick_fn"]
        return eps

    ref.compiled_entry_points = with_rogue
    try:
        with pytest.raises(KeyError, match="_rogue_fn"):
            audit_engine(ref)
    finally:
        del ref.compiled_entry_points


def test_tampered_contract_fails_on_real_engine(engines):
    """The gate has teeth against the real kernel tick: pinning the
    wrong launch count fails with the entry point and census named."""
    from repro.analysis import ContractViolation
    _, ker = engines
    bad = {"_tick_fn": CompiledContract("_tick_fn", launches=2,
                                        collectives=serve_collective_rule())}
    rep = audit_engine(ker, contracts=bad)
    assert not rep.ok
    with pytest.raises(ContractViolation, match="_tick_fn"):
        rep.raise_on_violation()


# ---------------------------------------------------------------------------
# retrace + transfer guard
# ---------------------------------------------------------------------------

def _stream(eng, prompts, max_new, stagger=0):
    import asyncio

    from repro.serving.orchestrator import Orchestrator
    orch = Orchestrator(eng)

    async def go():
        streams = [orch.schedule_arrival(after_tick=i * stagger,
                                         prompt=p, max_new_tokens=max_new)
                   for i, p in enumerate(prompts)]

        async def drain(s):
            async for _ in s:
                pass
        consumers = [asyncio.ensure_future(drain(s)) for s in streams]
        orch.close()
        done = await orch.serve()
        for c in consumers:
            await c
        return done

    return asyncio.run(go()), orch


def test_streamed_pressure_trace_zero_steady_retraces(rng):
    """Acceptance: a full streamed pressure-trace replay — prefix
    sharing, staggered arrivals, more requests than slots — performs
    ZERO retraces and zero implicit D2H syncs after the warmup batch
    (every dispatch runs under
    jax.transfer_guard_device_to_host('disallow'))."""
    eng = _engine("reference", prefix_cache=True)
    guard = RetraceGuard(eng).install()
    try:
        done, _ = _stream(eng, [rng.integers(0, 256, 12)
                                for _ in range(2)], max_new=8)
        assert len(done) == 2
        guard.mark_steady()
        shared = rng.integers(0, 256, 16)
        prompts = [np.concatenate([shared, rng.integers(0, 256, 4)])
                   for _ in range(5)]
        done, orch = _stream(eng, prompts, max_new=16, stagger=2)
        assert len(done) == 7     # scheduler's finished list is cumulative
        assert eng.metrics["prefix_hits"] > 0       # pressure was real
        guard.assert_steady_state()
        assert guard.steady_retraces() == 0
        assert sum(guard.calls.values()) > 10       # and it ran plenty
        assert not [e for e in orch.events if e["kind"] == "retrace"
                    and e["steady"]]
    finally:
        guard.uninstall()


def test_steady_state_retrace_fails_loudly(rng):
    """Deliberate violation: after warmup, a host caller passing a
    python int where a jnp.int32 belongs changes the jit signature —
    the guard attributes the retrace to the entry point and raises, and
    the orchestrator logs it."""
    eng = _engine("reference")
    guard = RetraceGuard(eng).install()
    try:
        _stream(eng, [rng.integers(0, 256, 10)], max_new=4)
        guard.mark_steady()
        fn_args = eng.compiled_entry_points()["_prefill_chunk_fn"]
        eng._prefill_chunk(*fn_args[1][:-1], 5)     # weak-typed scalar
        assert guard.steady_retraces() == 1
        with pytest.raises(RetraceViolation, match="_prefill_chunk"):
            guard.assert_steady_state()
        # the next streamed run folds the event into the metrics log
        _, orch = _stream(eng, [rng.integers(0, 256, 6)], max_new=4)
        assert any(e["kind"] == "retrace"
                   and e["entry"] == "_prefill_chunk"
                   for e in orch.events)
    finally:
        guard.uninstall()


# ---------------------------------------------------------------------------
# AST lint rules
# ---------------------------------------------------------------------------

def _lint():
    path = Path(__file__).resolve().parents[1] / "scripts" / \
        "lint_rules.py"
    spec = importlib.util.spec_from_file_location("lint_rules", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_lint_rules_repo_clean(capsys):
    assert _lint().main() == 0, capsys.readouterr().out


def test_lint_blocking_sync_fixture(tmp_path):
    lint = _lint()
    bad = tmp_path / "bad.py"
    bad.write_text(
        "import jax\n"
        "async def f(res):\n"
        "    res.block()\n"
        "    jax.device_get(res)\n"
        "def g(res):\n"
        "    res.block()\n")                # sync def: out of scope
    out = lint.lint_blocking_sync(bad)
    assert len(out) == 2
    assert "block" in out[0] and "device_get" in out[1]
    good = tmp_path / "good.py"
    good.write_text(
        "async def f(loop, res):\n"
        "    await loop.run_in_executor(None, res.block)\n")
    assert lint.lint_blocking_sync(good) == []


def test_lint_refcount_mutation_fixture(tmp_path):
    lint = _lint()
    bad = tmp_path / "bad.py"
    bad.write_text(
        "def f(pool, i):\n"
        "    pool = pool._replace(refcount=pool.refcount.at[i].add(1))\n"
        "    return pool\n")
    out = lint.lint_refcount_mutation([bad])
    assert len(out) == 2                    # the .at chain AND _replace
    ok = tmp_path / "ok.py"
    ok.write_text("def f(pool):\n    return pool.refcount.sum()\n")
    assert lint.lint_refcount_mutation([ok]) == []


def test_lint_float64_fixture(tmp_path):
    lint = _lint()
    bad = tmp_path / "bad.py"
    bad.write_text(
        "import jax.numpy as jnp\n"
        "import numpy as np\n"
        "a = jnp.float64(1.0)\n"
        "b = np.float64(2.0)\n"
        "c = 'float64'\n")
    out = lint.lint_float64([bad])
    assert len(out) == 3
    # the np allowlist admits host-side accumulation files only
    out = lint.lint_float64([bad], allow_np={str(bad)})
    assert len(out) == 3                    # tmp file not under src/repro


def test_engine_census_has_no_callbacks_or_fp64(engines):
    """The serving entry points are clean of host callbacks, in-graph
    transfers, and fp64 — asserted directly on the census (the contract
    check covers this too; this pins the censuses themselves)."""
    for eng in engines:
        for e in audit_engine(eng).entries.values():
            assert e.census.callbacks == []
            assert e.census.transfers == []
            assert e.census.fp64 == []
