"""Work the window layers' decode attention needs per tick: every window
layer's queries of every occupied slot against the ring positions that
slot holds (the engine's ``window_positions`` counter: positions
attended, over window layers and slots).

The count is of the algorithm at the stated bf16: each attended
position's key and value read once, each (layer, slot)'s queries read
and outputs written once; scores and the weighted sum of values, for
every query head.  Ring entries not yet written are not counted, so the
share cannot pass 100%.
"""
from __future__ import annotations

#: The tick's device scope that holds this attention
#: (``repro.serving.tracing.WINDOW_ATTENTION``).
SCOPE = "window_attention"
BF16 = 2


def count(positions: float, rows: float, q_heads: int, kv_heads: int,
          head_dim: int) -> tuple:
    """``(flops, bytes)`` of one tick: ``positions`` ring positions
    attended (over layers and slots), ``rows`` (window layer, slot)
    pairs."""
    flops = 4.0 * q_heads * head_dim * positions
    kv = 2.0 * positions * kv_heads * head_dim * BF16
    q_and_out = 2.0 * rows * q_heads * head_dim * BF16
    return flops, kv + q_and_out
