"""``attn_pages_share.reasoning`` reads the engine's page counters over
the traced window, and nothing from a program that lacks them."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from harness import context  # noqa: E402

NAME = "attn_pages_share.reasoning"


def _run(counters_open, counters_close, traced=True):
    t = context.Traced(events=[], t0=0.0, t1=5.0,
                       counters_open=counters_open,
                       counters_close=counters_close,
                       snap_open={}, snap_close={}) if traced else None
    return context.Run(dims={}, peaks={}, setup_s=0.0, records=[], open=0.0,
                       close=51.0, counters_open={}, counters_close={},
                       traced=t)


def _read(run):
    return context.load_module("metrics", NAME).read(run)


@pytest.mark.parametrize("opened,closed,share", [
    # 43 ticks of 6 x 64 x 64 table entries, ~33 of 64 mapped a row
    ({"attn_pages": 1000, "attn_page_slots": 24576},
     {"attn_pages": 1000 + 43 * 12672, "attn_page_slots": 24576 * 44},
     100.0 * 12672 / 24576),
    # every entry mapped
    ({"attn_pages": 0, "attn_page_slots": 0},
     {"attn_pages": 512, "attn_page_slots": 512}, 100.0),
])
def test_share_is_the_window_delta_of_the_counters(opened, closed, share):
    assert _read(_run(opened, closed)) == pytest.approx(share)


@pytest.mark.parametrize("opened,closed,traced", [
    ({"ticks": 1}, {"ticks": 44}, True),      # the parent: no counters
    ({}, {"attn_pages": 0, "attn_page_slots": 0}, True),    # no tick
    ({}, {"attn_pages": 9, "attn_page_slots": 18}, False),  # untraced
])
def test_nothing_to_read_gives_none(opened, closed, traced):
    assert _read(_run(opened, closed, traced)) is None
