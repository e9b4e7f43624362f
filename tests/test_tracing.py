"""The serving path's own tracing (``serving/tracing.py``): the tick's
named scopes reach the lowered HLO and change nothing else, the serve
loop's spans land on the profiler's host planes, and ``host_syncs``
counts the engine's blocking reads.

Tiny smoke config on the CPU; the tick is lowered, never compiled.
"""
import contextlib
import glob
import re

import jax
import numpy as np
import pytest

from repro.config import ServeConfig, ThinKVConfig
from repro.configs import get_smoke_config
from repro.serving import tracing as TR
from repro.serving.engine import ThinKVEngine
from repro.serving.orchestrator import Orchestrator

TK = ThinKVConfig(refresh_interval=16, group_size=8, block_size=8,
                  token_budget=48, retention_schedule=(16, 8, 4),
                  min_retention=4, max_segments=64, kmeans_iters=4)


def _engine(backend, slots=2):
    cfg = get_smoke_config("r1-llama-8b")
    return ThinKVEngine(ServeConfig(model=cfg, thinkv=TK, max_seqs=slots,
                                    temperature=0.0), backend=backend)


def _eqns(jaxpr):
    """Every equation of a jaxpr, sub-jaxprs included, in order."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A reference engine that served three requests under the
    profiler: ``(engine, streams, xplane path)``."""
    eng = _engine("reference")
    rng = np.random.default_rng(0)
    for traced in (False, True):    # compile first, outside the trace
        orch = Orchestrator(eng)
        streams = [orch.submit(rng.integers(0, 256, n), max_new_tokens=10)
                   for n in (5, 9, 7)]
        out = tmp_path_factory.mktemp("trace")
        with jax.profiler.trace(str(out)) if traced \
                else contextlib.nullcontext():
            orch.run_sync()
    (path,) = glob.glob(str(out / "**" / "*.xplane.pb"), recursive=True)
    return eng, streams, path


def test_tick_scopes_reach_the_lowered_hlo(served):
    eng, _, _ = served
    _, args = eng.compiled_entry_points()["_tick_fn"]
    text = eng._tick.lower(*args).as_text(debug_info=True)
    attrs = set(re.findall(rf'{TR.SCOPE_ATTR} = "([^"]*)"', text))
    op_names = [n.split("/") for n in re.findall(r'loc\("([^"]*)"', text)]
    # every phase names its operations by attribute; the reference tick
    # has no sparsity probe (its attention pass yields the sparsity)
    assert attrs == set(TR.PHASES) - {TR.PROBE}
    for name in set(TR.SCOPES) - {TR.PROBE}:
        assert any(name in n for n in op_names), name
    # the kernel tick: every scope is in the name stack that becomes its
    # operations' op_name, and each operation carries the attribute of
    # the innermost phase it runs in (the parts of advance carry advance)
    kern = _engine("kernel")
    fn, args = kern.compiled_entry_points()["_tick_fn"]
    named = set()
    for e in _eqns(jax.make_jaxpr(fn)(*args).jaxpr):
        stack = str(e.source_info.name_stack).split("/")
        named.update(stack)
        phases = [n for n in stack if n in TR.PHASES]
        want = TR.ADVANCE if set(stack) & set(TR.ADVANCE_PARTS) \
            else phases[-1] if phases else None
        if want is not None:
            assert e.ctx.xla_metadata[TR.SCOPE_ATTR] == want, stack
    assert set(TR.SCOPES) <= named


def test_serve_loop_spans_on_the_profiler_host_planes(served):
    _, streams, path = served
    assert all(len(s.request.output) == 10 for s in streams)
    from jax.profiler import ProfileData
    seen = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in TR.SPANS:
                    seen.setdefault(e.name, []).append(
                        (dict(e.stats), e.start_ns,
                         e.start_ns + e.duration_ns))
    assert set(seen) == set(TR.SPANS), set(TR.SPANS) - set(seen)
    arrivals = sorted(int(st["arrival"]) for st, _, _ in seen[TR.PREFILL])
    assert arrivals == sorted(s.request.arrival for s in streams)
    # one step per loop iteration; the tick counter runs on from the
    # warm-up episode, so the numbers are consecutive, not from 1
    steps = sorted(int(st["step_num"]) for st, _, _ in seen[TR.STEP])
    assert len(steps) >= 10 and len(set(steps)) >= 9

    def inside(child, parent):
        return all(any(a <= c0 and c1 <= b for _, a, b in seen[parent])
                   for _, c0, c1 in seen[child])
    # the executor thread's fetches lie inside the loop's wait
    assert inside(TR.FETCH_TOKENS, TR.WAIT)
    assert inside(TR.FETCH_LOGITS, TR.WAIT)
    assert inside(TR.HEADROOM, TR.DISPATCH) and inside(TR.LAUNCH,
                                                       TR.DISPATCH)
    assert inside(TR.PREFILL, TR.ADMIT)


def test_host_syncs_count_one_read_per_commit_due_headroom_check(served):
    eng, _, _ = served
    rng = np.random.default_rng(1)
    prompts = (5, 9)
    eng.submit([rng.integers(0, 256, n) for n in prompts],
               max_new_tokens=40)
    eng.scheduler.admit(eng._admission_gate())
    key = jax.random.PRNGKey(0)
    for slot in eng.scheduler.active_slots():
        prefix, key = eng.prefill(slot.request.prompt, slot.idx, key)
        eng.insert(prefix, slot.idx)
        slot.tokens_out += 1
    syncs = []
    for _ in range(16):
        before = eng.metrics["host_syncs"]
        res, key = eng.generate(key)
        eng.consume(res)
        syncs.append(eng.metrics["host_syncs"] - before)
    # tick t commits a group for the slot whose prompt p has
    # (p + t) % g == 0: g = 8 gives ticks 3, 11 and 7, 15; the headroom
    # check reads the free list once on each of them, and nothing else
    # in a tick reads the device outside the result fetch
    due = [t for t in range(1, 17)
           if any((p + t) % TK.group_size == 0 for p in prompts)]
    assert due == [3, 7, 11, 15]
    assert syncs == [1 if t in due else 0 for t in range(1, 17)]


def test_attn_pages_count_the_mapped_entries_each_tick_reads(served):
    eng, _, _ = served
    rng = np.random.default_rng(2)
    key = jax.random.PRNGKey(1)
    eng.submit([rng.integers(0, 256, n) for n in (6, 11)],
               max_new_tokens=40)
    for slot in eng.scheduler.admit(eng._admission_gate()):
        prefix, key = eng.prefill(slot.request.prompt, slot.idx, key)
        eng.insert(prefix, slot.idx)
        slot.tokens_out += 1
    before = dict(eng.metrics)
    mapped = []
    for _ in range(6):
        mapped.append(int((np.asarray(eng.tables) >= 0).sum()))
        res, key = eng.generate(key)
        eng.consume(res)
    assert eng.metrics["attn_pages"] - before["attn_pages"] == sum(mapped)
    assert eng.metrics["attn_page_slots"] - before["attn_page_slots"] == \
        6 * eng.tables.size
    # the slots hold a few pages each; most table entries are unmapped
    assert 0 < sum(mapped) < 6 * eng.tables.size // 2


def test_tick_scopes_are_metadata_only(served, monkeypatch):
    eng, _, _ = served
    fn, args = eng.compiled_entry_points()["_tick_fn"]

    def traced():
        jax.clear_caches()    # trace afresh (last in the file: this
                              # drops the engine's compiled programs)
        return list(_eqns(jax.make_jaxpr(fn)(*args).jaxpr))
    scoped = traced()
    monkeypatch.setattr(TR, "scope", lambda name: contextlib.nullcontext())
    plain = traced()
    assert any(e.ctx.xla_metadata for e in scoped)
    assert not any(e.ctx.xla_metadata for e in plain)
    assert [e.primitive.name for e in scoped] == \
        [e.primitive.name for e in plain]
    assert len(plain) > 100
