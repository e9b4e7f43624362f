"""Work the fused decode kernel ``ct_paged_attention_fused`` needs per
tick: every layer's queries of every occupied slot against that slot's
valid cached tokens and its TBQ buffer.

The count is of the algorithm, not of today's layout: each valid token is
read once, its codes at the width the slot metadata records (2 or 4 bits),
its scales as one e4m3 byte per group; the buffer's keys and values,
the queries and the outputs at the stated bf16.  Unmapped pages and the
byte per code that the pool uses today are not counted, so packing codes
or skipping pages raises the kernel's share of its roofline and cannot
push it past 100%.
"""
from __future__ import annotations

import numpy as np

from harness import cachestate

#: Regular expression that finds the kernel's events in a device trace.
PATTERN = r"ct_paged_attention_fused"
BF16 = 2


def count(snap: dict, q_heads: int) -> tuple:
    """``(flops, bytes)`` of one tick over the cache state ``snap``
    (``harness.cachestate.summarize``)."""
    H, D = snap["kv_heads"], snap["head_dim"]
    L = snap["layers"]
    active = np.asarray(snap["active"], bool)
    valid = float(cachestate.valid_tokens(snap).sum())
    buf = float(((np.asarray(snap["buf_len"]) + 1) * active).sum())
    attended = valid + L * buf
    flops = 4.0 * q_heads * D * attended
    kv_codes = 2.0 * float(cachestate.code_bits(snap).sum()) * H * D / 8
    kv_scales = 2.0 * valid * H * snap["scales_per_head"]
    kv_buffer = 2.0 * L * buf * H * D * BF16
    q_and_out = 2.0 * L * int(active.sum()) * q_heads * D * BF16
    return flops, kv_codes + kv_scales + kv_buffer + q_and_out
