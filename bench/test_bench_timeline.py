"""End-to-end arithmetic on synthetic client timelines."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from harness import timeline as TL  # noqa: E402
from harness.timeline import Record  # noqa: E402


def _batch(stall_at=None, stall=0.0, n_req=8, n_tok=200, tick=0.01):
    """``n_req`` requests decoding one token per tick from t=0; from
    ``stall_at`` every tick takes ``stall`` seconds longer."""
    recs = []
    for i in range(n_req):
        r = Record(i, due=0.0, prompt_len=10, max_new=n_tok)
        t = 0.0
        for k in range(n_tok):
            t += tick + (stall if stall_at is not None and t >= stall_at
                         else 0.0)
            r.stamps.append(t)
        recs.append(r)
    return recs


def _arrivals(stall_from=None, stall=0.0, n=200, gap=0.05, service=0.1):
    """Open-loop arrivals every ``gap``; first token ``service`` after
    due, plus ``stall`` for requests due after ``stall_from``."""
    recs = []
    for i in range(n):
        due = i * gap
        r = Record(i, due=due, prompt_len=10, max_new=4)
        extra = stall if stall_from is not None and due >= stall_from else 0
        first = due + service + extra
        r.stamps = [first + 0.01 * k for k in range(4)]
        recs.append(r)
    return recs


def test_stall_lowers_output_rate_and_raises_token_gaps():
    calm, stalled = _batch(), _batch(stall_at=1.0, stall=0.04)
    assert TL.token_rate(stalled, 0.0, 1.8) < TL.token_rate(calm, 0.0, 1.8)
    assert TL.p95(TL.token_gaps(stalled, 0.0, 1.8)) > \
        TL.p95(TL.token_gaps(calm, 0.0, 1.8))


def test_stall_raises_ttft_counted_from_due_time():
    calm, stalled = _arrivals(), _arrivals(stall_from=5.0, stall=0.5)
    assert TL.p95(TL.first_token_waits(stalled, 0.0, 10.0)) > \
        TL.p95(TL.first_token_waits(calm, 0.0, 10.0))


def test_request_without_token_counts_as_window_end():
    r = Record(0, due=1.0, prompt_len=4, max_new=4)
    assert TL.first_token_waits([r], 0.0, 3.0) == [2.0]
    r.stamps = [3.5]                      # after the window: still the end
    assert TL.first_token_waits([r], 0.0, 3.0) == [2.0]


def test_only_tokens_inside_the_window_count():
    r = Record(0, due=0.0, prompt_len=4, max_new=4)
    r.stamps = [0.5, 1.5, 2.5, 3.5]
    assert TL.delivered([r], 1.0, 3.0) == 2
    assert TL.token_gaps([r], 1.0, 3.0) == [1.0, 1.0]
