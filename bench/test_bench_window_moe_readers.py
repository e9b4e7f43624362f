"""The window-attention and held-expert readers: work counts by hand,
the hit share as the window delta of the engine's counters, and nothing
read from a program without the counters or scopes."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from harness import context  # noqa: E402

window = context.load_module("kernels", "window_attention")
experts = context.load_module("kernels", "held_experts")
READERS = ("expert_hit_share.mellum2", "window_attn_roofline.mellum2",
           "moe_roofline.mellum2", "decode_mfu.mellum2")


def _run(counters_open, counters_close, traced=True):
    t = context.Traced(events=[], t0=0.0, t1=5.0,
                       counters_open=counters_open,
                       counters_close=counters_close,
                       snap_open={}, snap_close={}) if traced else None
    return context.Run(dims={}, peaks={}, setup_s=0.0, records=[], open=0.0,
                       close=51.0, counters_open={}, counters_close={},
                       traced=t)


def test_window_count_by_hand():
    # two (layer, slot) rows attending 1024 and 300 ring positions
    flops, nbytes = window.count(1324, 2, 32, 4, 128)
    assert flops == 4 * 32 * 128 * 1324
    assert nbytes == 2 * 1324 * 4 * 128 * 2 + 2 * 2 * 32 * 128 * 2


def test_held_experts_count_by_hand():
    # one layer, 3 tokens, router over 64; 2 experts hit by 5 pairs
    flops, nbytes = experts.count(2, 5, 3, 1, 2304, 896, 64)
    assert flops == 2 * 3 * 2304 * 64 + 6 * 5 * 2304 * 896
    weights = (2304 * 64 + 2 * 3 * 2304 * 896) * 2
    rows = (3 * 2304 + 2 * 5 * 2304) * 2
    assert nbytes == weights + rows


def test_hit_share_is_the_window_delta_of_the_counters():
    opened = {"experts_hit": 100, "expert_slots": 128}
    closed = {"experts_hit": 100 + 40 * 120, "expert_slots": 128 * 41}
    got = context.load_module("metrics", READERS[0]).read(
        _run(opened, closed))
    assert got == pytest.approx(100.0 * 120 / 128)


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("opened,closed,traced", [
    ({"ticks": 1}, {"ticks": 44}, True),      # the parent: no counters
    ({}, {"experts_hit": 9, "expert_slots": 18, "expert_assignments": 9,
          "window_positions": 9, "ticks": 1, "tokens": 1}, False),
])
def test_nothing_to_read_gives_none(name, opened, closed, traced):
    reader = context.load_module("metrics", name)
    assert reader.read(_run(opened, closed, traced)) is None
