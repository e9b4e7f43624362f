"""Whole-step share of the chip's bf16 peak while decoding: model FLOPs
per output token at the cut depth (every matmul, counted at the stated
bf16, plus attention over the valid cached tokens and the buffer, from
the cache state at the trace's start and end) times the output tokens
per second delivered in the traced window, over the peak."""
import numpy as np

from harness import cachestate, timeline


def _attended(snap) -> float:
    """Mean tokens one layer of one occupied slot attends."""
    act = np.asarray(snap["active"], bool)
    if not act.any():
        return 0.0
    per_slot = cachestate.valid_tokens(snap).mean(axis=1) + \
        np.asarray(snap["buf_len"]) + 1
    return float(per_slot[act].mean())


def read(run):
    t = run.traced
    if t is None:
        return None
    m = run.dims
    att = (_attended(t.snap_open) + _attended(t.snap_close)) / 2
    per_token = 2.0 * run.matmul_params() + 4.0 * m["L"] * m["hq"] * \
        m["hd"] * att
    rate = timeline.token_rate(run.records, t.t0, t.t1)
    return 100.0 * per_token * rate / run.peaks["bf16_flops_per_s"]
