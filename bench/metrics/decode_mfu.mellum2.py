"""Whole-step share of the chip's bf16 peak while decoding, for a model
with window and full attention layers and held experts: model FLOPs per
output token at the stated bf16 times the output tokens per second
delivered in the traced window, over the peak.

FLOPs per token: every layer's q, k, v and output projections; each
full layer's attention over its valid cached tokens and buffer (from
the cache state at the trace's start and end) and each window layer's
over the ring positions it attended (``window_positions`` per token);
the router over every published expert; the held experts over the
(token, expert) pairs routed to them (``expert_assignments`` per token,
this chip's share of the top-k); and the LM head.  ``Run.matmul_params``
counts a dense MLP and is not used."""
import numpy as np

from harness import cachestate, timeline


def _attended(snap) -> float:
    """Mean tokens one full layer of one occupied slot attends."""
    act = np.asarray(snap["active"], bool)
    if not act.any():
        return 0.0
    per_slot = cachestate.valid_tokens(snap).mean(axis=1) + \
        np.asarray(snap["buf_len"]) + 1
    return float(per_slot[act].mean())


def read(run):
    t = run.traced
    if t is None or "expert_assignments" not in t.counters_close:
        return None
    tokens = t.counter("tokens")
    if tokens <= 0:
        return None
    m = run.dims
    n_window = sum(m["window"])
    n_full = m["L"] - n_window
    proj = 2.0 * m["d"] * (2 * m["hq"] * m["hd"] + 2 * m["hkv"] * m["hd"])
    full = (_attended(t.snap_open) + _attended(t.snap_close)) / 2
    attn = 4.0 * m["hq"] * m["hd"] * (
        n_full * full + t.counter("window_positions") / tokens)
    experts = 2.0 * m["L"] * m["d"] * m["E"] + \
        6.0 * m["d"] * m["ff"] * t.counter("expert_assignments") / tokens
    per_token = m["L"] * proj + attn + experts + 2.0 * m["d"] * m["V"]
    rate = timeline.token_rate(run.records, t.t0, t.t1)
    return 100.0 * per_token * rate / run.peaks["bf16_flops_per_s"]
