"""``correct`` on a small model with window and full attention layers and
held experts, on the CPU: a sound run is correct; the reference's fp8
control in the program's place, and a tick whose cache never takes the
step in (rings included), are not.

As ``test_bench_correct.py``, with the ``window_moe`` system and
reference (``testdata/tiny-window-moe.json``: 4 layers "window, window,
window, full", window 32, 4 of 8 experts held, 8-bit codes, budget 64):
weights from the seed, the engine on the kernel backend (interpret mode
here), warm-up, priming until every cache is past its budget, the window
through ``Orchestrator.serve()``, and the comparison at the close.
"""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

import run as R  # noqa: E402
from harness import faults  # noqa: E402

CFG = json.loads((BENCH / "testdata" / "tiny-window-moe.json").read_text())
MIX = json.loads((BENCH / "testdata" / "tiny-batch.json").read_text())
CELL = {"name": "tiny-window-moe.batch", "config": "tiny-window-moe",
        "traffic": "tiny-batch", "chips": 1}
SEED = 3
# the window closes with every slot in flight: this model runs ~150
# ticks a second on the CPU, and its requests ~180 tokens past the budget
SECONDS = 0.5


@pytest.fixture(scope="module")
def built():
    return R.build(CFG, MIX, SEED, log=lambda s: None)


def _run(built, **kw):
    return R.run_cell(CELL, CFG, MIX, {}, seed=SEED, seconds=SECONDS,
                      trace=False, log=lambda s: None, built=built, **kw)


@pytest.mark.parametrize("case", ["sound", "state_unchanged"])
def test_correct_holds_only_for_the_sound_window_moe_path(built, case):
    limit = CFG["limits"]["max_gap"]
    if case == "sound":
        out = _run(built, control=True)
        got = out["compared"]
        assert out["correct"], got
        assert got["positions"] >= 8
        assert not out["control_correct"], got
        assert out["control_checks"]["max_gap"]["value"] > limit
    else:
        out = _run(built, fault=faults.FAULTS[case])
        assert not out["correct"], out["compared"]
