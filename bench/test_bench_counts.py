"""Kernel counts follow the cache state, never its layout."""
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))

from harness import cachestate, context  # noqa: E402

VALID = 1
fused = context.load_module("kernels", "ct_paged_attention_fused")


def _state(R=3, L=2, NS=64, seed=0):
    rng = np.random.default_rng(seed)
    state = rng.choice([0, 1, 2], size=(R, L, NS), p=[0.3, 0.5, 0.2])
    bits = rng.choice([2, 4], size=(R, L, NS))
    return state.astype(np.uint8), bits.astype(np.uint8), \
        rng.integers(0, 16, R), np.array([True, False, True])


def _count(state, bits, buf, active):
    snap = cachestate.summarize(state, bits, buf, active, VALID, 4, 128, 8)
    return fused.count(snap, 28)


def test_same_state_in_another_layout_counts_the_same():
    state, bits, buf, active = _state()
    base = _count(state, bits, buf, active)
    rng = np.random.default_rng(1)
    perm = rng.permutation(state.shape[-1])          # tokens moved around
    assert _count(state[..., perm], bits[..., perm], buf, active) == base
    order = [2, 1, 0]                                # slots reordered
    assert _count(state[order], bits[order], buf[order],
                  active[order]) == base
    junk = state.copy()                              # unused slot's content
    junk[1] = VALID
    assert _count(junk, bits, buf, active) == base


def test_fused_count_by_hand():
    # one slot, one layer: 3 valid tokens at 4 bits, 1 at 2 bits, 2 in
    # the buffer (plus the token being decoded)
    state = np.array([[[1, 1, 1, 1, 2, 0]]], np.uint8)
    bits = np.array([[[4, 4, 4, 2, 4, 4]]], np.uint8)
    snap = cachestate.summarize(state, bits, [2], [True], VALID, 4, 128, 8)
    flops, bytes_ = fused.count(snap, 28)
    assert flops == 4 * 28 * 128 * (4 + 3)
    codes = 2 * (3 * 4 + 2) * 4 * 128 / 8
    scales = 2 * 4 * 4 * 8
    buffer = 2 * 3 * 4 * 128 * 2
    q_out = 2 * 28 * 128 * 2
    assert bytes_ == codes + scales + buffer + q_out
