"""Quickstart: the ThinKV core API in 60 lines.

    PYTHONPATH=src python examples/quickstart.py

1. quantize a KV group at thought-adaptive precision (TBQ);
2. build a CT paged cache and stream tokens through it (TBE + CT);
3. read compression stats and run paged decode attention.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ThinKVConfig, ThoughtType
from repro.core import ct_cache as CC
from repro.core import quantization as Q
from repro.core import thinkv as TV

rng = np.random.default_rng(0)

# --- 1. TBQ: NVFP4 group quantization (R/E thoughts) --------------------
x = jnp.asarray(rng.standard_normal((16, 128)), jnp.float32)
codes, scales = Q.quantize_group(x, bits=4)           # e2m1 + e4m3 scales
x_hat = Q.dequantize_group(codes, scales, bits=4)
print(f"NVFP4 roundtrip rel-RMSE: "
      f"{float(jnp.linalg.norm(x - x_hat) / jnp.linalg.norm(x)):.3f}")

# --- 2. a CT cache for a 2-layer toy model ------------------------------
# the paged split: CTCache carries metadata + the fp TBQ buffer, PoolView
# carries the quantized planes in paged [L, NB, H, BS, ...] layout
tk = ThinKVConfig(refresh_interval=16, group_size=8, block_size=8,
                  token_budget=64, retention_schedule=(16, 8, 4),
                  min_retention=4, max_segments=64, kmeans_iters=4)
dims = CC.make_dims(tk, num_layers=2, kv_heads=2, head_dim=32)
cache = CC.init_cache(dims)
view = CC.init_pool_view(dims)
step = jax.jit(functools.partial(TV.step_token, tk, dims))

# planted sparsity: R -> E -> T -> R windows (Sec. 3.1 tri-modal signal)
sparsity = {0: 0.65, 1: 0.30, 2: 0.92, 3: 0.65}
for i in range(200):
    k = jnp.asarray(rng.standard_normal((2, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 2, 32)), jnp.float32)
    cache, view = step(cache, view, k, v,
                       jnp.float32(sparsity[(i // 16) % 4]))

stats = TV.compression_ratio(tk, dims, cache, jnp.int32(200))
print(f"after 200 tokens: {int(CC.valid_counts(cache)[0])} retained/layer, "
      f"avg {float(stats['avg_bits']):.2f} bits, "
      f"{float(stats['footprint_frac']) * 100:.1f}% of FullKV bytes")
print("segment types (0=T,1=E,2=R):",
      np.asarray(cache.seg_type[:int(cache.cur_seg) + 1]))

# --- 3. paged decode attention over the compressed cache ----------------
q = jnp.asarray(rng.standard_normal((4, 32)), jnp.float32)
out = TV.decode_attention_ref(dims, cache, view, q, layer=0)
print("decode attention out:", out.shape, "finite:",
      bool(jnp.isfinite(out).all()))

# --- 4. the refcounted GlobalPool: share, COW, release ------------------
# the serving engine's physical pool: blocks are claimed at commits,
# SHARED across requests by the prefix cache (refcount++), and any write
# to a shared block copy-on-write faults into a private copy
pool = CC.init_global_pool(dims, num_blocks=2 * dims.NB)
table = CC.init_block_table(dims)
spars = jnp.float32(0.65)
for i in range(dims.G):
    k = jnp.asarray(rng.standard_normal((2, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 2, 32)), jnp.float32)
    gcache = CC.init_cache(dims) if i == 0 else gcache
    gcache = gcache.replace(
        buf_k=gcache.buf_k.at[:, i].set(k.astype(jnp.bfloat16)),
        buf_v=gcache.buf_v.at[:, i].set(v.astype(jnp.bfloat16)))
    pool, table, gcache = CC.engine_advance(tk, dims, pool, table, gcache,
                                            spars, jnp.bool_(True))
pool = CC.incref_blocks(dims, pool, table)        # a second holder
shared = int((np.asarray(pool.refcount) > 1).sum())
pool, table2, ok = CC.cow_blocks(dims, pool, table, table >= 0)
CC.check_pool_invariants(pool, np.stack([np.asarray(table),
                                         np.asarray(table2)]))
print(f"global pool: {shared} shared block refs, COW ok={bool(ok)}, "
      f"invariants hold (claimed + free == pool_blocks)")
