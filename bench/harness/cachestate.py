"""The cache state the kernel counts read, as plain numbers.

What a kernel's work depends on is, per occupied slot and layer, how many
cached tokens are valid at each code width, and how many tokens the TBQ
buffer holds.  Where those tokens sit in the slot array or in pool pages
does not enter, so a layout change cannot change the count.
"""
from __future__ import annotations

import numpy as np


def summarize(state, bits, buf_len, active, valid_code: int, kv_heads: int,
              head_dim: int, scales_per_head: int) -> dict:
    """``state``/``bits`` ``[R, L, slots]``, ``buf_len``/``active`` ``[R]``."""
    state, bits = np.asarray(state), np.asarray(bits)
    active = np.asarray(active, bool)
    valid = (state == valid_code) & active[:, None, None]
    widths = sorted({int(b) for b in np.unique(bits[valid])})
    return {"active": active,
            "valid_by_bits": {b: ((bits == b) & valid).sum(-1)
                              for b in widths},
            "layers": int(state.shape[1]),
            "buf_len": np.asarray(buf_len) * active,
            "kv_heads": kv_heads, "head_dim": head_dim,
            "scales_per_head": scales_per_head}


def valid_tokens(snap: dict) -> np.ndarray:
    """Valid cached tokens per slot and layer ``[R, L]``."""
    n = len(snap["active"])
    out = np.zeros((n, snap["layers"]), np.int64)
    for v in snap["valid_by_bits"].values():
        out = out + v
    return out


def code_bits(snap: dict) -> np.ndarray:
    """Summed code width of the valid tokens per slot and layer."""
    n = len(snap["active"])
    out = np.zeros((n, snap["layers"]), np.int64)
    for b, v in snap["valid_by_bits"].items():
        out = out + b * v
    return out
