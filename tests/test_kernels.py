"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ThinKVConfig
from repro.core import ct_cache as CC
from repro.core import thinkv as TV
from repro.kernels import ops
from repro.kernels import ref as R
from repro.kernels.ct_paged_attention import ct_paged_attention
from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.group_quant import group_quant


# ---------------------------------------------------------------------------
# group_quant
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", (2, 4, 8))
@pytest.mark.parametrize("shape", ((16, 32), (48, 128), (128, 256)))
@pytest.mark.parametrize("dtype", (jnp.float32, jnp.bfloat16))
def test_group_quant_kernel_vs_ref(rng, bits, shape, dtype):
    x = jnp.asarray(rng.standard_normal(shape), dtype)
    ck, sk = group_quant(x, bits, interpret=True)
    cr, sr = R.group_quant_ref(x.astype(jnp.float32), bits)
    assert (np.asarray(ck) == np.asarray(cr)).all()
    np.testing.assert_allclose(np.asarray(sk, np.float32),
                               np.asarray(sr, np.float32), rtol=1e-2)


# ---------------------------------------------------------------------------
# flash_prefill
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s,hq,h,d", [(128, 4, 4, 32), (256, 8, 2, 64),
                                      (256, 8, 1, 64)])
@pytest.mark.parametrize("window", (0, 96))
def test_flash_prefill_vs_ref(rng, s, hq, h, d, window):
    q = jnp.asarray(rng.standard_normal((s, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((s, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((s, h, d)), jnp.float32)
    o_k = flash_prefill(q, k, v, causal=True, window=window, block_q=64,
                        block_k=64, interpret=True)
    o_r = R.flash_prefill_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               rtol=3e-5, atol=3e-5)


def test_flash_prefill_stats_vs_ref(rng):
    """return_stats variant: out AND (m, l) match the oracle (the stats
    feed the chunked-prefill partition merge)."""
    s, hq, h, d = 128, 4, 2, 64
    q = jnp.asarray(rng.standard_normal((s, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((s, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((s, h, d)), jnp.float32)
    o_k, m_k, l_k = flash_prefill(q, k, v, block_q=64, block_k=64,
                                  interpret=True, return_stats=True)
    o_r, m_r, l_r = R.flash_prefill_stats_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(l_k), np.asarray(l_r),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(m_k), np.asarray(m_r),
                               rtol=3e-5, atol=3e-5)


def test_flash_prefill_bf16(rng):
    s, hq, h, d = 128, 4, 2, 64
    q = jnp.asarray(rng.standard_normal((s, hq, d)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((s, h, d)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((s, h, d)), jnp.bfloat16)
    o_k = flash_prefill(q, k, v, block_q=64, block_k=64, interpret=True)
    o_r = R.flash_prefill_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# ct_paged_attention
# ---------------------------------------------------------------------------

def _cache_args(rng, kv_heads=2, head_dim=64, steps=120, layers=1,
                precision=(2, 4, 4)):
    cfg = ThinKVConfig(refresh_interval=32, group_size=16, block_size=16,
                       token_budget=64, retention_schedule=(16, 8, 4),
                       min_retention=4, max_segments=32, kmeans_iters=4,
                       precision=precision)
    dims = CC.make_dims(cfg, num_layers=layers, kv_heads=kv_heads,
                        head_dim=head_dim, slack=2.0)
    cache = CC.init_cache(dims)
    view = CC.init_pool_view(dims)
    step = jax.jit(functools.partial(TV.step_token, cfg, dims))
    spars = [0.6, 0.3, 0.9, 0.65]
    for i in range(steps):
        k = jnp.asarray(rng.standard_normal((layers, kv_heads, head_dim)),
                        jnp.float32)
        v = jnp.asarray(rng.standard_normal((layers, kv_heads, head_dim)),
                        jnp.float32)
        cache, view = step(cache, view, k, v,
                           jnp.float32(spars[(i // 32) % 4]))
    args = (view.k_codes[0], view.v_codes[0],
            view.k_scales[0], view.v_scales[0],
            cache.slot_state[0].reshape(dims.NB, dims.BS),
            cache.slot_bits[0].reshape(dims.NB, dims.BS),
            jnp.arange(dims.NB, dtype=jnp.int32))
    return cfg, dims, cache, view, args


@pytest.mark.parametrize("hq_mult", (1, 4))
@pytest.mark.parametrize("head_dim", (32, 64, 128))
def test_ct_paged_attention_vs_ref(rng, hq_mult, head_dim):
    kv_heads = 2
    _, dims, cache, view, args = _cache_args(rng, kv_heads, head_dim)
    q = jnp.asarray(rng.standard_normal((kv_heads * hq_mult, head_dim)),
                    jnp.float32)
    o_k, m_k, l_k = ct_paged_attention(q, *args, group=16, interpret=True)
    o_r, m_r, l_r = R.ct_paged_attention_ref(q, *args, group=16)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(l_k), np.asarray(l_r),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("precision", ((2, 4, 4), (2, 4, 8), (8, 8, 8)))
@pytest.mark.parametrize("hq_mult", (1, 2, 4))
def test_ct_paged_attention_bitwidth_gqa_sweep(rng, precision, hq_mult):
    """Kernel parity across stored bit-widths {2,4,8} (via the precision
    policy + scripted thought pattern) and GQA group sizes, with evicted
    slots present from budget pressure."""
    kv_heads = 2
    _, dims, cache, view, args = _cache_args(rng, kv_heads, 64,
                                             precision=precision)
    assert bool(np.any(np.asarray(cache.slot_state[0]) == 2)), \
        "sweep must exercise evicted slots"
    q = jnp.asarray(rng.standard_normal((kv_heads * hq_mult, 64)),
                    jnp.float32)
    o_k, _, l_k = ct_paged_attention(q, *args, group=16, interpret=True)
    o_r, _, l_r = R.ct_paged_attention_ref(q, *args, group=16)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(l_k), np.asarray(l_r),
                               rtol=3e-5, atol=3e-5)


def test_ct_paged_attention_block_table_indirection(rng):
    """Shuffled physical pool + matching table == identity layout."""
    kv_heads, head_dim = 2, 64
    _, dims, cache, view, args = _cache_args(rng, kv_heads, head_dim)
    q = jnp.asarray(rng.standard_normal((8, head_dim)), jnp.float32)
    o_id, _, _ = ct_paged_attention(q, *args, group=16, interpret=True)
    perm = np.asarray(rng.permutation(dims.NB), np.int32)
    shuffled = []
    for a in args[:-1]:
        buf = np.zeros_like(np.asarray(a))
        buf[perm] = np.asarray(a)
        shuffled.append(jnp.asarray(buf))
    o_sh, _, _ = ct_paged_attention(q, *shuffled, jnp.asarray(perm),
                                    group=16, interpret=True)
    np.testing.assert_allclose(np.asarray(o_sh), np.asarray(o_id),
                               rtol=1e-5, atol=1e-5)


def test_ct_paged_attention_batched_vs_ref(rng):
    """Batched launch (shared pool + per-request tables) == per-request
    single-launch results."""
    kv_heads, head_dim, R_ = 2, 64, 3
    _, dims, cache, view, args = _cache_args(rng, kv_heads, head_dim)
    kc, vc, ks, vs, state, bits, _ = args
    # build a shared physical pool holding R shuffled copies
    NB = dims.NB
    perms = [np.asarray(rng.permutation(NB), np.int32) for _ in range(R_)]
    pool_kc = np.zeros((R_ * NB,) + kc.shape[1:], np.asarray(kc).dtype)
    pool_vc = np.zeros_like(pool_kc)
    pool_ks = np.zeros((R_ * NB,) + ks.shape[1:], np.float32)
    pool_vs = np.zeros_like(pool_ks)
    tables = np.zeros((R_, NB), np.int32)
    for r, perm in enumerate(perms):
        phys = r * NB + perm
        pool_kc[phys] = np.asarray(kc)
        pool_vc[phys] = np.asarray(vc)
        pool_ks[phys] = np.asarray(ks, np.float32)
        pool_vs[phys] = np.asarray(vs, np.float32)
        tables[r] = phys
    qs = rng.standard_normal((R_, 8, head_dim)).astype(np.float32)
    qh = jnp.asarray(qs).reshape(R_, kv_heads, 4, head_dim)
    o_b, m_b, l_b = ops.paged_decode_attention_batched(
        qh, jnp.asarray(pool_kc), jnp.asarray(pool_vc),
        jnp.asarray(pool_ks, jnp.bfloat16), jnp.asarray(pool_vs, jnp.bfloat16),
        jnp.broadcast_to(state[None], (R_, NB, dims.BS)),
        jnp.broadcast_to(bits[None], (R_, NB, dims.BS)),
        jnp.asarray(tables), group=16, force="pallas")
    for r in range(R_):
        o_s, _, l_s = R.ct_paged_attention_ref(
            jnp.asarray(qs[r]), kc, vc, ks, vs, state, bits,
            jnp.arange(NB, dtype=jnp.int32), group=16)
        np.testing.assert_allclose(np.asarray(o_b[r]).reshape(8, head_dim),
                                   np.asarray(o_s), rtol=3e-5, atol=3e-5)
        np.testing.assert_allclose(np.asarray(l_b[r]), np.asarray(l_s),
                                   rtol=3e-5, atol=3e-5)


def _fused_args(rng, layers, kv_heads=2, head_dim=64, precision=(2, 4, 8),
                requests=2):
    """Build fused-kernel inputs from a REAL CT cache evolution (evicted +
    free slot mixes from budget pressure) with ``layers`` stacked layers,
    plus random fp TBQ buffers and raw block tables with -1 sentinels."""
    _, dims, cache, view, _ = _cache_args(rng, kv_heads, head_dim,
                                          layers=layers, precision=precision)
    assert bool(np.any(np.asarray(cache.slot_state) == 2)), \
        "sweep must exercise evicted slots"
    assert bool(np.any(np.asarray(cache.slot_state) == 0)), \
        "sweep must exercise free slots"
    L, NB, BS, G = dims.L, dims.NB, dims.BS, dims.G
    state = np.asarray(cache.slot_state).reshape(L, NB, BS)
    bits = np.asarray(cache.slot_bits).reshape(L, NB, BS)
    state_r = np.broadcast_to(state[:, None], (L, requests, NB, BS)).copy()
    bits_r = np.broadcast_to(bits[:, None], (L, requests, NB, BS)).copy()
    # identity tables; the last request leaves fully-FREE blocks unmapped
    # (-1 sentinel) to exercise the raw-table entry-point clamp
    tables = np.broadcast_to(np.arange(NB, dtype=np.int32)[None, None],
                             (requests, L, NB)).copy()
    block_free = ~(state == 1).any(axis=2) & ~(state == 2).any(axis=2)
    for l in range(L):
        tables[-1, l][block_free[l]] = -1
    buf_k = rng.standard_normal((L, requests, dims.H, G, dims.D))
    buf_v = rng.standard_normal((L, requests, dims.H, G, dims.D))
    buf_len = np.linspace(0, G, requests).astype(np.int32)
    return dims, dict(
        k_codes=view.k_codes, v_codes=view.v_codes,
        k_scales=view.k_scales, v_scales=view.v_scales,
        slot_state=jnp.asarray(state_r), slot_bits=jnp.asarray(bits_r),
        block_table=jnp.asarray(tables),
        buf_k=jnp.asarray(buf_k, jnp.bfloat16),
        buf_v=jnp.asarray(buf_v, jnp.bfloat16),
        buf_len=jnp.asarray(buf_len))


@pytest.mark.parametrize("layers,precision", [(1, (2, 4, 4)), (2, (2, 4, 8)),
                                              (4, (8, 8, 8))])
@pytest.mark.parametrize("hq_mult", (1, 2, 4))
def test_ct_paged_attention_fused_vs_ref(rng, layers, precision, hq_mult):
    """Fused-layer sweep: the single-launch (L, R, H, NB+1)-grid kernel
    (pool + folded TBQ-buffer merge) matches the layered reference across
    layer counts, GQA ratios, bit-widths, and evicted/free slot mixes —
    within the 1e-3 acceptance bound (observed ~1e-5)."""
    from repro.kernels.ct_paged_attention import ct_paged_attention_fused
    kv_heads, head_dim = 2, 64
    dims, args = _fused_args(rng, layers, kv_heads, head_dim, precision)
    R_ = args["block_table"].shape[0]
    qh = jnp.asarray(rng.standard_normal(
        (layers, R_, kv_heads, hq_mult, head_dim)), jnp.float32)
    o_k = ct_paged_attention_fused(qh, **args, group=16, interpret=True)
    o_r = R.ct_paged_attention_fused_ref(qh, **args, group=16)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               rtol=1e-4, atol=1e-4)


def test_ct_paged_attention_fused_is_one_launch(rng):
    """The fused entry point stages exactly ONE pallas_call regardless of
    layer count (the launch-amortization contract)."""
    from repro.kernels.ct_paged_attention import ct_paged_attention_fused
    _, args = _fused_args(rng, layers=4)
    qh = jnp.asarray(rng.standard_normal((4, 2, 2, 2, 64)), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda q, a: ct_paged_attention_fused(q, **a, group=16,
                                              interpret=True))(qh, args)
    assert ops.count_pallas_launches(jaxpr) == 1


#: table rows left unmapped, as {(request, layer): logical blocks mapped}
#: (every other row maps all NB = 8 blocks)
LAYOUTS = {
    "full": {},
    "holes": {(0, 0): (0, 2, 3, 5, 7), (0, 1): (0, 2, 3, 5, 7)},
    "empty_row": {(1, 1): ()},
    "empty_chunk": {(0, 0): (6, 7), (1, 1): (0,)},
}
SPARE = 3                         # physical blocks no table maps


@pytest.fixture(scope="module")
def paged_case():
    """Fused-kernel inputs over a shuffled physical pool with SPARE
    unmapped blocks, physical block 0 among them (the block the old grid
    clamped ``-1`` to): ``(qh, args, poisoned planes)``."""
    rng = np.random.default_rng(7)
    dims, args = _fused_args(rng, layers=2, precision=(2, 4, 8))
    L, NB = dims.L, dims.NB
    phys = 1 + rng.permutation(NB + SPARE - 1)[:NB]
    pool, poisoned = {}, {}
    for name in ("k_codes", "v_codes", "k_scales", "v_scales"):
        a = np.asarray(args[name].astype(jnp.float32))
        out = np.zeros((L, NB + SPARE) + a.shape[2:], np.float32)
        out[:, phys] = a
        bad = out.copy()
        spare = np.setdiff1d(np.arange(NB + SPARE), phys)
        if "scales" in name:
            bad[:, spare] = np.nan
        else:
            bad[:, spare] = rng.integers(0, 256, bad[:, spare].shape)
        dt = args[name].dtype
        pool[name], poisoned[name] = jnp.asarray(out, dt), jnp.asarray(bad,
                                                                       dt)
    args = dict(args, **pool,
                block_table=jnp.broadcast_to(jnp.asarray(phys, jnp.int32),
                                             args["block_table"].shape))
    qh = jnp.asarray(rng.standard_normal((L, 2, dims.H, 2, dims.D)),
                     jnp.float32)
    return qh, args, poisoned


def _with_layout(args, layout):
    """``args`` with the rows of ``LAYOUTS[layout]`` partly unmapped: the
    dropped blocks read ``-1`` and their slots FREE."""
    table = np.asarray(args["block_table"]).copy()
    state = np.asarray(args["slot_state"]).copy()
    for (r, l), keep in LAYOUTS[layout].items():
        drop = np.setdiff1d(np.arange(table.shape[-1]), keep)
        table[r, l, drop] = -1
        state[l, r, drop] = 0
    return dict(args, block_table=jnp.asarray(table),
                slot_state=jnp.asarray(state))


def _fused(qh, args, pages):
    """The public entry point (chunk size from shapes) or, with
    ``pages``, the launch at that chunk size."""
    from repro.kernels import ct_paged_attention as CPA
    if pages is None:
        return CPA.ct_paged_attention_fused(qh, **args, group=16,
                                            interpret=True)
    launch = jax.jit(CPA._fused_launch,
                     static_argnames=("group", "pages", "interpret"))
    return launch(qh, **args, group=16, pages=pages, interpret=True)


@pytest.mark.parametrize("layout,pages", [
    ("holes", 3),            # holes mid-row; NB = 8 is not a multiple of 3
    ("holes", None),         # the derived chunk: every page in one step
    ("full", 3),             # every block mapped, a short last chunk
    ("empty_row", 2),        # one (request, layer) row maps nothing
    ("empty_chunk", 2),      # rows whose later chunks hold no mapped page
])
def test_ct_paged_attention_fused_table_layouts_vs_ref(paged_case, layout,
                                                       pages):
    """Multi-page grid steps over mapped-first rows: any set of mapped
    blocks, in any positions and any chunking, attends as the layered
    reference does; a row with nothing mapped attends its buffer only."""
    qh, args, _ = paged_case
    args = _with_layout(args, layout)
    o_k = np.asarray(_fused(qh, args, pages))
    o_r = np.asarray(R.ct_paged_attention_fused_ref(qh, **args, group=16))
    np.testing.assert_allclose(o_k, o_r, rtol=1e-4, atol=1e-4)
    if layout == "empty_row":
        o_b, _, _ = R.buffer_attention_batched_ref(
            qh[1], jnp.swapaxes(args["buf_k"][1], 1, 2),
            jnp.swapaxes(args["buf_v"][1], 1, 2), args["buf_len"])
        np.testing.assert_allclose(o_k[1, 1], np.asarray(o_b[1]),
                                   rtol=1e-5, atol=1e-5)


def test_ct_paged_attention_fused_never_reads_unmapped_blocks(paged_case):
    """NaN scales in physical block 0 and every other block no table maps
    leave the output finite and equal to the reference over the clean
    pool: unmapped pages are neither fetched nor let through unmasked
    from stale VMEM.  (The reference itself clamps ``-1`` to block 0, so
    it is computed over the clean pool.)"""
    qh, args, poisoned = paged_case
    args = _with_layout(args, "holes")
    o_r = np.asarray(R.ct_paged_attention_fused_ref(qh, **args, group=16))
    o_k = np.asarray(_fused(qh, dict(args, **poisoned), 3))
    assert np.isfinite(o_k).all()
    np.testing.assert_allclose(o_k, o_r, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("heads,pages", [(4, 16), (1, 64), (8, 8)])
def test_fused_chunk_is_derived_from_shapes(heads, pages):
    """At head_dim 128, g 16, BS 16 and NB 64: qwen2-7b's and yi-6b's 4
    kv heads, one head of a 4-way shard, and 8 kv heads."""
    from repro.kernels.ct_paged_attention import pages_per_step
    assert pages_per_step(64, heads, 16, 128, 8) == pages
    assert pages_per_step(5, heads, 16, 128, 8) <= 5


def test_batched_entry_accepts_raw_tables(rng):
    """Entry points clamp -1 sentinels internally: a raw table with
    unmapped (all-FREE) blocks matches the pre-clamped call."""
    kv_heads, head_dim = 2, 64
    _, dims, cache, view, args = _cache_args(rng, kv_heads, head_dim)
    kc, vc, ks, vs, state, bits, table = args
    state_np = np.asarray(state)
    free_blocks = ~(state_np != 0).any(axis=1)
    assert free_blocks.any(), "need at least one fully-free block"
    raw = np.asarray(table).copy()
    raw[free_blocks] = -1
    q = jnp.asarray(rng.standard_normal((8, head_dim)), jnp.float32)
    o_raw, _, l_raw = ct_paged_attention(q, kc, vc, ks, vs, state, bits,
                                         jnp.asarray(raw), group=16,
                                         interpret=True)
    o_ref, _, l_ref = R.ct_paged_attention_ref(q, kc, vc, ks, vs, state,
                                               bits, table, group=16)
    np.testing.assert_allclose(np.asarray(o_raw), np.asarray(o_ref),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(l_raw), np.asarray(l_ref),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("chunk", (128, 256))
def test_large_chunk_prefill_kernel_vs_chunked_ref(rng, chunk):
    """Large-chunk prefill parity: a 128-multiple chunk through the
    compiled ``flash_prefill`` kernel (stats variant) matches the chunked
    reference oracle — the intra-chunk partition of the engine's
    large-chunk prefill mode."""
    hq, h, d = 4, 2, 64
    q = jnp.asarray(rng.standard_normal((chunk, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((chunk, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((chunk, h, d)), jnp.float32)
    o_k, m_k, l_k = ops.prefill_attention_stats(q, k, v, causal=True,
                                                force="pallas")
    o_r, m_r, l_r = R.flash_prefill_stats_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(m_k), np.asarray(m_r),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(l_k), np.asarray(l_r),
                               rtol=3e-5, atol=3e-5)


def test_prefill_kernel_path_refuses_partial_tile(rng):
    """An unpadded chunk on the kernel path that is not a 128-multiple
    raises instead of dropping to the oracle; a padded chunk (kv_valid
    given) takes the oracle by design."""
    q = jnp.asarray(rng.standard_normal((64, 4, 32)), jnp.float32)
    kv = jnp.asarray(rng.standard_normal((64, 2, 32)), jnp.float32)
    with pytest.raises(ValueError, match="128-multiple"):
        ops.prefill_attention_stats(q, kv, kv, force="pallas")
    valid = jnp.arange(64) < 40
    o, _, _ = ops.prefill_attention_stats(q, kv, kv, kv_valid=valid,
                                          force="pallas")
    o_r, _, _ = R.flash_prefill_stats_ref(q, kv, kv, kv_valid=valid)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(o_r))


def test_full_thinkv_attention_kernel_path(rng):
    """Kernel + B_buf merge == reference decode attention."""
    cfg, dims, cache, view, _ = _cache_args(rng, 2, 64, steps=90)
    q = jnp.asarray(rng.standard_normal((8, 64)), jnp.float32)
    o_full = ops.thinkv_decode_attention(dims, cache, view, q, 0,
                                         force="pallas")
    o_ref = TV.decode_attention_ref(dims, cache, view, q, 0)
    np.testing.assert_allclose(np.asarray(o_full), np.asarray(o_ref),
                               rtol=3e-4, atol=3e-4)


# ---------------------------------------------------------------------------
# mamba_scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s,di,n", [(64, 128, 16), (128, 256, 16),
                                    (96, 64, 8)])
def test_mamba_scan_kernel_vs_ref(rng, s, di, n):
    from repro.kernels.mamba_scan import mamba_scan
    x = jnp.asarray(rng.standard_normal((s, di)), jnp.float32)
    dt = jnp.asarray(0.01 + 0.1 * rng.random((s, di)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((s, n)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((s, n)), jnp.float32)
    a = jnp.asarray(-np.exp(rng.standard_normal((di, n))), jnp.float32)
    y_k = mamba_scan(x, dt, b, c, a, d_block=64, chunk=32, interpret=True)
    y_r = R.mamba_scan_ref(x, dt, b, c, a)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=3e-4, atol=3e-4)


def test_mamba_scan_matches_layer_semantics(rng):
    """Kernel == the model's _mamba1_inner recurrence on matched inputs."""
    from repro.kernels.mamba_scan import mamba_scan
    s, di, n = 64, 32, 8
    x = jnp.asarray(rng.standard_normal((s, di)), jnp.float32)
    dt = jnp.asarray(0.01 + 0.2 * rng.random((s, di)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((s, n)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((s, n)), jnp.float32)
    a = jnp.asarray(-np.exp(rng.standard_normal((di, n))), jnp.float32)
    y_k = mamba_scan(x, dt, b, c, a, d_block=32, chunk=16, interpret=True)
    # replicate via the numpy recurrence
    h = np.zeros((di, n))
    for t in range(s):
        da = np.exp(np.asarray(dt)[t][:, None] * np.asarray(a))
        h = da * h + (np.asarray(dt)[t] * np.asarray(x)[t])[:, None] * \
            np.asarray(b)[t][None, :]
        np.testing.assert_allclose(np.asarray(y_k)[t],
                                   (h * np.asarray(c)[t][None, :]).sum(1),
                                   rtol=3e-4, atol=3e-4)


def test_merge_flash_identity(rng):
    """Merging a partition with an empty partition returns the partition."""
    h, gq, d = 2, 4, 32
    out = jnp.asarray(rng.standard_normal((h * gq, d)), jnp.float32)
    m = jnp.asarray(rng.standard_normal((h, gq, 1)), jnp.float32)
    l = jnp.asarray(rng.random((h, gq, 1)) + 0.5, jnp.float32)
    empty_o = jnp.zeros_like(out)
    empty_m = jnp.full((h, gq, 1), -1e30)
    empty_l = jnp.zeros((h, gq, 1))
    merged = R.merge_flash_ref(out, m, l, empty_o, empty_m, empty_l)
    np.testing.assert_allclose(np.asarray(merged), np.asarray(out),
                               rtol=1e-6, atol=1e-6)
