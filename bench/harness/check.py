"""The comparison that decides ``correct``.

At the window's close the harness keeps the engine's cache metadata as
the last dispatched step left it (which positions each layer of each slot
holds, the length of the uncompressed buffer, how many tokens the cache
has taken in) and which request each slot serves (``at_close`` in the
configuration's ``systems/`` file).  Once the run has stopped and the
engine is freed, a sample of those requests, drawn from the seed and
always with the longest context among them, goes through the plain
reference, teacher-forced on each prompt and the tokens the engine fed
back.

The positions compared are the tokens each slot was fed since its last
group commit.  Those ticks ran inside the window, in its last ``g``
ticks, with every slot's cache at ``token_budget``, and nothing was
committed or evicted after them, so each attended exactly the positions
the close records as held, plus the buffer.  The reference attends the
same positions, computing every key, value and logit itself; the gap is
how far the served token's reference logit lies below the reference's
best.  Greedy serving of the same function gives 0 up to rounding and the
cache's 2- and 4-bit codes.

Besides the gap (``max_gap``, its limit in the configuration file) each
run holds the engine to what the close records, over every request in
flight at the close:

* ``fed_mismatch``: requests whose cache has not taken in exactly its
  prompt and every served token but the last (a step that did not land
  in the cache, or a token served that was never fed);
* ``held_bad``: held positions the request has not yet committed, or held
  twice in one layer;
* ``held_over_budget``: the most positions one layer holds, less
  ``token_budget``; the engine bounds it by one group (``group_size``);

and over every request: ``short_requests`` (finished in the window with
fewer tokens than asked) and ``delivered_mismatch`` (a client received
tokens other than those the engine produced).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np

from harness.timeline import Record

GAP = "max_gap"


@dataclasses.dataclass
class Live:
    """One request in flight at the close."""
    rec: Record
    prompt: np.ndarray
    held: np.ndarray          # [L, P + k] positions each layer holds
    buf_start: int            # first position in the buffer
    fed: int                  # k: served tokens the cache has taken in

    @property
    def rows(self) -> np.ndarray:
        """Indices of fed tokens since the last commit."""
        return np.arange(max(0, self.buf_start - len(self.prompt)),
                         self.fed)

    @property
    def context(self) -> int:
        return len(self.prompt) + self.fed


def in_flight(state: dict, live: Dict[int, int], records: List[Record],
              prompts: dict, budget: int) -> tuple:
    """``(candidates, numbers)``: the requests in flight at the close that
    have positions to compare, and the close's own checks over all of
    them (``fed_mismatch``, ``held_bad``, ``held_over_budget``)."""
    by_index = {r.index: r for r in records}
    cands, mismatch, bad, over = [], 0, 0, None
    for slot, idx in sorted(live.items()):
        rec, prompt = by_index[idx], prompts[idx]
        P, N = len(prompt), int(state["num_tokens"][slot])
        if N < P:
            continue                     # still in prefill at the close
        k = N - P
        if len(rec.served) != k + 1:
            mismatch += 1
            continue
        b = int(state["buf_len"][slot])
        held = state["held"][slot]
        pos = state["pos"][slot]
        mask = np.zeros((held.shape[0], N), bool)
        for layer in range(held.shape[0]):
            p = pos[layer][held[layer]]
            ok = (p >= 0) & (p < N - b)
            bad += int((~ok).sum()) + int(p[ok].size - np.unique(p[ok]).size)
            mask[layer, p[ok]] = True
        counts = held.sum(-1)
        over = max(int(counts.max()) - budget,
                   over if over is not None else -budget)
        c = Live(rec, prompt, mask, N - b, k)
        if c.rows.size:
            cands.append(c)
    return cands, {"fed_mismatch": mismatch, "held_bad": bad,
                   "held_over_budget": over}


def sample(cands: List[Live], check: dict, seed: int) -> List[Live]:
    """The longest context, then others in an order drawn from ``seed``,
    until ``served_tokens`` positions are covered or ``max_requests``
    are taken."""
    if not cands:
        return []
    longest = max(cands, key=lambda c: (c.context, -c.rec.index))
    rest = [c for c in cands if c is not longest]
    rng = np.random.default_rng([seed, 0x5EED])
    picked, total = [longest], longest.rows.size
    for i in rng.permutation(len(rest)):
        if total >= check["served_tokens"] or \
                len(picked) >= check["max_requests"]:
            break
        picked.append(rest[i])
        total += rest[i].rows.size
    return picked


def prompt_pad(mix: dict, block: int) -> int:
    top = int(mix["prompt_tokens"]["max"])
    return -(-top // block) * block


def compare(reference, cfg: dict, weights, picked: List[Live], pad: int,
            control: bool = False) -> dict:
    """Gaps at the compared positions of ``picked``: the widest (and
    with ``control`` the control's widest), how many positions and
    requests, and per request ``[prompt, fed, compared, widest gap]``."""
    worst = {"gap": None, "ctl": None}
    n, per = 0, []
    for c in picked:
        served = np.asarray(c.rec.served, np.int32)
        rows = c.rows
        g, ctl = reference.served_gaps(
            cfg, weights, c.prompt, served[:c.fed], c.held, c.buf_start,
            rows, served[rows + 1], pad, control=control)
        for key, v in (("gap", g), ("ctl", ctl)):
            worst[key] = max(float(v.max()), worst[key] or 0.0)
        n += rows.size
        per.append([len(c.prompt), c.fed, int(rows.size), float(g.max())])
    out = {GAP: worst["gap"], "positions": n, "requests": len(picked),
           "per_request": per}
    if control:
        out["control_gap"] = worst["ctl"]
    return out


def whole_run(records: List[Record], close: float) -> dict:
    """``short_requests`` and ``delivered_mismatch`` over every request."""
    short = sum(1 for r in records if r.finished and r.ended is not None
                and r.ended < close and len(r.tokens) != r.max_new)
    wrong = sum(1 for r in records
                if r.tokens != r.served[:len(r.tokens)])
    return {"short_requests": short, "delivered_mismatch": wrong}


def verdict(result: dict, numbers: dict, limit, group: int,
            gap: str = GAP) -> tuple:
    """``(correct, checks)``: ``result[gap]`` within ``limit`` over at
    least one compared position, and each of ``numbers`` within its own
    limit.  The control's result passes ``gap="control_gap"``."""
    checks = {
        GAP: {"value": result.get(gap), "limit": limit},
        "positions_compared": {"value": result.get("positions", 0),
                               "limit": 1},
        "fed_mismatch": {"value": numbers["fed_mismatch"], "limit": 0},
        "held_bad": {"value": numbers["held_bad"], "limit": 0},
        "held_over_budget": {"value": numbers["held_over_budget"],
                             "limit": group},
        "short_requests": {"value": numbers["short_requests"], "limit": 0},
        "delivered_mismatch": {"value": numbers["delivered_mismatch"],
                               "limit": 0},
    }
    v = checks[GAP]["value"]
    ok = (limit is not None and v is not None and v <= limit
          and checks["positions_compared"]["value"] >= 1
          and all(checks[k]["value"] is not None
                  and checks[k]["value"] <= checks[k]["limit"]
                  for k in checks if k not in (GAP, "positions_compared")))
    return ok, checks
