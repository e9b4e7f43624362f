"""The trace reduction, on a small trace recorded on the chip and on a
hand-made one."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from harness import trace as T  # noqa: E402

RECORDED = Path(__file__).resolve().parent / "testdata" / "tpu_trace.json"
DEV, HOST = "/device:TPU:0", "/host:CPU"


def _ev(plane, line, name, start, dur):
    return T.Event(plane, line, name, float(start), float(dur))


def _hand():
    return [
        _ev(HOST, "main", T.WINDOW_SPAN, 0, 100),
        _ev(HOST, "main", "engine.generate", 0, 30),
        _ev(HOST, "pool", "result.wait", 40, 50),
        _ev(DEV, T.MODULES_LINE, "jit_tick(1)", 10, 40),
        _ev(DEV, T.OPS_LINE, "ct_paged_attention_fused", 10, 20),
        _ev(DEV, T.OPS_LINE, "%fusion.3 = f32[8] fusion(%a)", 25, 25),
        _ev(DEV, T.OPS_LINE, "fusion.7", 70, 10),
        _ev(DEV, T.OPS_LINE, "copy.1", 95, 15),         # runs past the end
        _ev(DEV, T.OPS_LINE, "copy.2", -20, 5),         # before the window
    ]


def test_busy_union_and_idle_on_hand_trace():
    ev = _hand()
    # busy: [10, 50) + [70, 80) + [95, 100) = 55 ns
    assert T.busy_seconds(ev) == pytest.approx(55e-9)
    gaps = dict(T.idle_gaps_by_span(ev, ["engine.generate", "result.wait"]))
    # idle [0,10) under generate; [50,70) and [80,90) under result.wait;
    # [90,95) under no span
    assert gaps == pytest.approx({"engine.generate": 10e-9,
                                  "result.wait": 30e-9,
                                  "host.other": 5e-9})


def test_kernel_module_and_top_ops_on_hand_trace():
    ev = _hand()
    assert T.seconds_of(ev, T.OPS_LINE, "ct_paged_attention_fused") == \
        pytest.approx(20e-9)
    assert T.seconds_of(ev, T.MODULES_LINE, r"jit_tick") == \
        pytest.approx(40e-9)
    top = T.top_ops(ev)
    assert top[0] == ["fusion.3", pytest.approx(25e-9)]
    assert [n for n, _ in top] == ["fusion.3", "ct_paged_attention_fused",
                                   "copy.1", "fusion.7"]


def _recorded():
    raw = json.loads(RECORDED.read_text())
    return [T.Event(*e) for e in raw["events"]], raw


def test_recorded_busy_matches_a_coverage_sweep():
    ev, raw = _recorded()
    lo, hi = T.window(ev)
    plane = T.device_planes(ev)[0]
    iv = np.array([(max(e.start, lo), min(e.end, hi)) for e in ev
                   if e.plane == plane and e.line == T.OPS_LINE
                   and e.end > lo and e.start < hi])
    # +1 at every start, -1 at every end; busy where the count is above 0
    t = np.concatenate([iv[:, 0], iv[:, 1]])
    step = np.concatenate([np.ones(len(iv)), -np.ones(len(iv))])
    order = np.lexsort((-step, t))
    t, depth = t[order], np.cumsum(step[order])
    busy = float(np.sum(np.diff(t)[depth[:-1] > 0])) / 1e9
    assert T.busy_seconds(ev) == pytest.approx(busy, rel=1e-9)
    assert T.busy_seconds(ev) == pytest.approx(raw["busy_s"], rel=1e-9)


def test_recorded_idle_gaps_cover_the_idle_time():
    ev, raw = _recorded()
    lo, hi = T.window(ev)
    gaps = T.idle_gaps_by_span(ev, raw["spans"], n=100)
    assert sum(s for _, s in gaps) == pytest.approx(
        (hi - lo) / 1e9 - T.busy_seconds(ev), rel=1e-9)


def test_recorded_kernel_and_module_times():
    ev, raw = _recorded()
    lo, hi = T.window(ev)
    for pattern, want in raw["kernel_s"].items():
        manual = sum(e.dur for e in ev if T.is_device(e.plane)
                     and e.line == T.OPS_LINE and lo <= e.start < hi
                     and (pattern in e.name or pattern in e.detail)) / 1e9
        assert manual > 0
        assert T.seconds_of(ev, T.OPS_LINE, pattern) == pytest.approx(manual)
        assert manual == pytest.approx(want)
    for pattern, want in raw["module_s"].items():
        assert T.seconds_of(ev, T.MODULES_LINE, pattern) == \
            pytest.approx(want)
