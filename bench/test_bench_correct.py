"""``correct`` on a small dense GQA model on the CPU: a sound run is
correct; the reference's fp8 control in the program's place, and each
fault planted under the timed path from the window's open, are not.

The harness's look for a chip is skipped (``run.run_cell`` is called
directly); everything after it runs: weights from the seed, the engine on
the kernel backend (interpret mode here), warm-up, priming until every
cache is past its budget, the window through ``Orchestrator.serve()``,
and the comparison with the plain reference at the close.
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

CFG = json.loads((BENCH / "testdata" / "tiny.json").read_text())
MIX = json.loads((BENCH / "testdata" / "tiny-batch.json").read_text())
CELL = {"name": "tiny.batch", "config": "tiny", "traffic": "tiny-batch",
        "chips": 1}
SEED = 3
SECONDS = 2.0            # the window closes with every slot in flight


@pytest.fixture(scope="module")
def built():
    return R.build(CFG, MIX, SEED, log=lambda s: None)


def _run(built, **kw):
    return R.run_cell(CELL, CFG, MIX, {}, seed=SEED, seconds=SECONDS,
                      trace=False, log=lambda s: None, built=built, **kw)


@pytest.mark.parametrize("case", ["sound"] + sorted(faults.FAULTS))
def test_correct_holds_only_for_the_sound_path(built, case):
    limit = CFG["limits"]["max_gap"]
    if case == "sound":
        out = _run(built, control=True)
        got = out["compared"]
        assert out["correct"], got
        assert got["positions"] >= 8
        # some cache was at its budget at the close
        assert got["held_over_budget"] >= -CFG["engine"]["group_size"]
        # the control, judged in the program's place, is not correct
        assert not out["control_correct"], got
        assert out["control_checks"]["max_gap"]["value"] > limit
    else:
        out = _run(built, fault=faults.FAULTS[case])
        assert not out["correct"], out["compared"]
