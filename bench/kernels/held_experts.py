"""Work the held-expert layers need per tick: the router over every
published expert for every occupied slot, and each held expert that
received a token (the engine's ``experts_hit`` counter, over layers)
applied to the (token, expert) pairs routed to it
(``expert_assignments``, over layers).

The count is of the algorithm at the stated bf16: the router's weights
and each hit expert's gate, up and down projections read once, each
assigned pair's input row read and output row written once; the
router's and the experts' multiply-adds.  Experts no token reached are
not counted, so the share cannot pass 100%.
"""
from __future__ import annotations

#: The tick's device scope that holds this layer
#: (``repro.serving.tracing.MOE``).
SCOPE = "moe"
BF16 = 2


def count(hit: float, pairs: float, tokens: float, layers: int, d: int,
          ff: int, experts: int) -> tuple:
    """``(flops, bytes)`` of one tick: ``hit`` held experts with a token
    and ``pairs`` assigned (token, expert) pairs, summed over the
    ``layers``; ``tokens`` occupied slots; ``experts`` the router's
    width."""
    flops = 2.0 * layers * tokens * d * experts + 6.0 * pairs * d * ff
    weights = (layers * d * experts + hit * 3.0 * d * ff) * BF16
    rows = (layers * tokens * d + 2.0 * pairs * d) * BF16
    return flops, weights + rows
