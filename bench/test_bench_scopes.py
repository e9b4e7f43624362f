"""The readings from the program's own spans and scopes
(``harness/scopes.py``), on hand-made traces and on a step recorded on
the chip."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

from harness import context, scopes  # noqa: E402
from harness import trace as T  # noqa: E402
from repro.serving import tracing as TR  # noqa: E402

RECORDED = BENCH / "testdata" / "tpu_trace_scopes.json"
DEV, HOST = "/device:TPU:0", "/host:CPU"
NEW = ("maintenance_device_ms.reasoning", "probe_device_ms.reasoning",
       "dispatch_idle_ms.reasoning", "fetch_idle_ms.reasoning",
       "host_syncs_per_tick.reasoning")


def _op(name, scope, start, dur):
    attr = f', frontend_attributes={{{TR.SCOPE_ATTR}="{scope}"}}' \
        if scope else ""
    return T.Event(DEV, T.OPS_LINE, f"%{name} = f32[2] {name}(){attr}",
                   float(start), float(dur))


def _span(name, start, dur, line="python"):
    return T.Event(HOST, line, name, float(start), float(dur))


def _run(events, counters_open=None, counters_close=None):
    traced = context.Traced(events=events, t0=0.0, t1=1.0,
                            counters_open=counters_open or {},
                            counters_close=counters_close or {},
                            snap_open={}, snap_close={})
    return context.Run(dims={}, peaks={}, setup_s=0.0, records=[], open=0.0,
                       close=1.0, counters_open={}, counters_close={},
                       traced=traced)


def _read(name, run):
    return context.load_module("metrics", name).read(run)


def test_a_while_and_its_nested_body_count_once():
    ev = [_span(T.WINDOW_SPAN, 0, 1000),
          T.Event(DEV, T.MODULES_LINE, "jit_tick(7)", 100, 400),
          _op("while.1", TR.ADVANCE, 200, 100),             # [200, 300)
          _op("cond.2", TR.ADVANCE, 210, 50),               # its body
          _op("fusion.3", TR.ADVANCE, 250, 80),             # to 330
          _op("cond.4", TR.PROBE, 340, 20),
          _op("fusion.5", TR.TRUNK, 100, 90),
          _op("fusion.6", None, 370, 10)]                   # no scope
    assert scopes.scope_ms_per_tick(ev, TR.ADVANCE) == pytest.approx(130e-6)
    assert scopes.scope_ms_per_tick(ev, TR.PROBE) == pytest.approx(20e-6)
    assert scopes.scope_ms_per_tick(ev, TR.TRUNK) == pytest.approx(90e-6)
    # a scope is matched by name, not as a substring of another name
    assert scopes.scope_ms_per_tick(ev, "adv") == 0.0


def test_scope_time_is_clipped_to_the_window_and_the_tick_program():
    adv = TR.ADVANCE
    ev = [_span(T.WINDOW_SPAN, 0, 1000),
          T.Event(DEV, T.MODULES_LINE, "jit_tick(7)", 900, 300),
          T.Event(DEV, T.MODULES_LINE, "jit_chunk_step(3)", 100, 300),
          _op("while.1", adv, 950, 200),                    # to 1150
          _op("while.9", adv, 150, 100)]                    # prefill's
    # one tick in the window; its advance runs [950, 1000) inside it
    assert scopes.scope_ms_per_tick(ev, TR.ADVANCE) == pytest.approx(50e-6)


def test_idle_under_a_span_is_attributed_and_clipped_to_the_window():
    ev = [_span(T.WINDOW_SPAN, 100, 900),                   # [100, 1000)
          T.Event(DEV, T.MODULES_LINE, "jit_tick(7)", 300, 400),
          _op("fusion.1", None, 300, 400),                  # busy [300, 700)
          _span(TR.DISPATCH, 0, 350),                       # idle 100-300
          _span(TR.WAIT, 350, 450),                         # idle 700-800
          _span(TR.DISPATCH, 950, 200)]                     # idle 950-1000
    assert scopes.idle_ms_per_tick(ev, TR.DISPATCH) == pytest.approx(250e-6)
    assert scopes.idle_ms_per_tick(ev, TR.WAIT) == pytest.approx(100e-6)
    assert scopes.idle_ms_per_tick(ev, TR.DELIVER) is None   # never open


def test_spans_on_two_host_threads_are_both_read():
    ev = [_span(T.WINDOW_SPAN, 0, 1000),
          T.Event(DEV, T.MODULES_LINE, "jit_tick(7)", 0, 100),
          _op("fusion.1", None, 0, 100),
          _span(TR.WAIT, 100, 100, line="main"),              # the loop's
          _span(TR.WAIT, 500, 100, line="ThreadPoolExecutor-0_0")]
    assert scopes.idle_ms_per_tick(ev, TR.WAIT) == pytest.approx(200e-6)
    spans = scopes.span_intervals(ev, TR.WAIT, 0, 1000)
    assert spans == [(100.0, 200.0), (500.0, 600.0)]


def test_a_program_without_the_names_reads_nothing():
    # the parent's trace: no scope attribute on any operation, only the
    # harness's own dotted spans, no host_syncs counter
    ev = [_span(T.WINDOW_SPAN, 0, 1000),
          T.Event(DEV, T.MODULES_LINE, "jit_tick(7)", 0, 500),
          _op("while.1", None, 0, 400),
          _span("engine.generate", 500, 100),
          _span("result.wait", 600, 300)]
    run = _run(ev, {"ticks": 0}, {"ticks": 40})
    assert all(_read(name, run) is None for name in NEW)
    assert all(_read(name, _run([])) is None for name in NEW[-1:])


def _recorded():
    raw = json.loads(RECORDED.read_text())
    ev = [T.Event(raw["planes"][p], raw["lines"][ln], raw["names"][n],
                  raw["t0"] + s, d) for p, ln, n, s, d in raw["events"]]
    return ev, raw


def test_recorded_step_reads_the_chip_run_numbers():
    ev, raw = _recorded()
    run = _run(ev, raw["counters_open"], raw["counters_close"])
    for name in NEW:
        assert _read(name, run) == pytest.approx(raw["metrics"][name]), name
    # the readings fit inside the step's own device time and idle time
    lo, hi = T.window(ev)
    ticks = scopes.tick_count(ev)
    tick_ms = _read("tick_device_ms.reasoning", run)
    idle_ms = (hi - lo - T.busy_seconds(ev) * 1e9) / 1e6 / ticks
    assert ticks == 1
    assert _read(NEW[0], run) + _read(NEW[1], run) <= tick_ms
    assert _read(NEW[2], run) + _read(NEW[3], run) <= idle_ms + 1e-9


def test_recorded_scope_time_matches_a_coverage_sweep():
    ev, _ = _recorded()
    lo, hi = T.window(ev)
    (tick,) = [e for e in ev if e.line == T.MODULES_LINE
               and "jit_tick" in e.name]
    a, b = max(lo, tick.start), min(hi, tick.end)
    for scope in (TR.ADVANCE, TR.PROBE):
        iv = np.array([(max(e.start, a), min(e.end, b)) for e in ev
                       if e.line == T.OPS_LINE and e.end > a and e.start < b
                       and scope in (scopes.scope_path(e, TR.SCOPE_ATTR)
                                     or "").split("/")])
        # +1 at every start, -1 at every end; covered where above 0
        t = np.concatenate([iv[:, 0], iv[:, 1]])
        step = np.concatenate([np.ones(len(iv)), -np.ones(len(iv))])
        order = np.lexsort((-step, t))
        t, depth = t[order], np.cumsum(step[order])
        covered = float(np.sum(np.diff(t)[depth[:-1] > 0])) / 1e6
        assert scopes.scope_ms_per_tick(ev, scope) == \
            pytest.approx(covered, rel=1e-9)
