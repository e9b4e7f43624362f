"""The traffic generator repeats exactly from a seed."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from harness import spec, traffic  # noqa: E402

MIXES = sorted(p.stem for p in (spec.BENCH / "traffic").glob("*.json"))
BIG_SEED = 2 ** 31 + 987654


@pytest.mark.parametrize("mix", MIXES)
def test_one_seed_repeats_exactly(mix):
    m = spec.mix(mix)
    a = traffic.make_requests(m, 1000, BIG_SEED, 10.0)
    b = traffic.make_requests(m, 1000, BIG_SEED, 10.0)
    assert [(r.due_s, r.max_new) for r in a] == [(r.due_s, r.max_new)
                                                for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_share_sizes_and_order_as_the_mix_says(mix):
    m = spec.mix(mix)
    a = traffic.make_requests(m, 1000, 1, 10.0)
    b = traffic.make_requests(m, 1000, 2, 10.0)
    assert [(len(r.prompt), r.max_new, r.due_s) for r in a] == \
        [(len(r.prompt), r.max_new, r.due_s) for r in b]
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    lo, hi = m["prompt_tokens"]["min"], m["prompt_tokens"]["max"]
    assert all(lo <= len(r.prompt) <= hi for r in a)
    assert all(0 <= int(r.prompt.min()) and int(r.prompt.max()) < 1000
               for r in a)


def test_poisson_arrivals_cover_priming_and_window():
    m = {"arrival": {"kind": "poisson", "rate_per_s": 5.0},
         "prompt_tokens": {"median": 8, "sigma": 0.5, "min": 2, "max": 20},
         "output_tokens": {"median": 8, "sigma": 0.5, "min": 2, "max": 20},
         "prime": {"kind": "seconds", "seconds": 4}}
    reqs = traffic.make_requests(m, 100, 3, 40.0)
    assert len(reqs) == 220
    due = [r.due_s for r in reqs]
    assert due == sorted(due) and due[0] == 0.0
    assert 40.0 < due[-1] < 48.0
