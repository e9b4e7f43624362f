"""Property test: refcounted global-pool accounting invariants under
random admit/step(commit/evict)/retire/preempt/resume/share/COW
sequences — on a single device AND on a head-sharded device mesh.

Across ANY interleaving — including allocation failures under an
oversubscribed pool (claims reverted), spill/resume cycles, prefix-style
SHARING (a second holder increfs a request's blocks), and explicit or
commit-triggered copy-on-write faults — every layer must satisfy:

* every physical block's refcount equals the number of live references
  to it (block tables + cached holders — no leak, no phantom ref);
* no refcount is negative (no double-free);
* ``claimed(refcount > 0) + free(refcount == 0) == pool_blocks``.

Additionally:

* a resumed request's pool planes must equal its spilled planes on every
  mapped block (restore is bit-exact);
* a SHARED holder's planes are content-immutable: from incref to
  release, the cached blocks' pool content never changes — any writer
  COW-faults into a private copy (or, on a failed COW claim, skips the
  write entirely) rather than mutating in place.

SHARDED VARIANT (8-device mesh, kv heads sharded over ``model``): the
commit/evict step runs inside ``shard_map`` exactly like the serving
engine's tick (planes/buffers head-local, metadata replicated,
``axis_name`` threaded into ``engine_advance`` for the TBE key gather
and COW dirty-mask reduction), and after EVERY op the test additionally
asserts that every shard agrees on the refcounts and the block tables —
the replicated pool accounting must never diverge across devices.  The
sharded test re-execs itself in a subprocess with 8 forced host devices
(same pattern as test_distributed.py)."""
import functools

import jax
import jax.numpy as jnp
from jax.sharding import AxisType
import numpy as np
import pytest

from _prop import given, settings, strategies as st
from conftest import has_mesh_devices, run_in_mesh_subprocess
from repro.config import ThinKVConfig
from repro.core import ct_cache as CC

_HAS_MESH_DEVS = has_mesh_devices()

TK = ThinKVConfig(refresh_interval=8, group_size=4, block_size=4,
                  token_budget=16, retention_schedule=(8, 4),
                  min_retention=2, max_segments=16, kmeans_iters=2)
DIMS = CC.make_dims(TK, num_layers=2, kv_heads=2, head_dim=16)
# head-shardable geometry for the 8-device mesh variant
DIMS8 = CC.make_dims(TK, num_layers=2, kv_heads=8, head_dim=16)
N_REQ = 3
N_KINDS = 6


def _pool_blocks(dims):
    # oversubscribed: room for ~1.5 requests' worst case across 3 requests
    return dims.NB + dims.NB // 2


@functools.lru_cache(maxsize=None)
def _make_step(dims, sharded: bool):
    """The commit/evict step, optionally shard_map'd over the KV-head
    axis exactly like the engine's tick (metadata replicated, planes and
    TBQ buffer head-local, axis_name threaded into engine_advance)."""
    ax = "model" if sharded else None
    nshard = 8 if sharded else 1

    def step(pool, table, cache, k, v, spars):
        if ax is not None:
            from repro.kernels import ops as K
            k = K.local_heads(k, 1, ax, nshard)      # [L, H, D] -> H/N
            v = K.local_heads(v, 1, ax, nshard)
        i = cache.buf_len
        cache = cache.replace(
            buf_k=jax.lax.dynamic_update_index_in_dim(
                cache.buf_k, k.astype(jnp.bfloat16)[:, None], i, 1),
            buf_v=jax.lax.dynamic_update_index_in_dim(
                cache.buf_v, v.astype(jnp.bfloat16)[:, None], i, 1))
        return CC.engine_advance(TK, dims, pool, table, cache, spars,
                                 jnp.bool_(True), with_alloc_fail=True,
                                 axis_name=ax)

    if not sharded:
        return jax.jit(step)
    from jax.sharding import PartitionSpec as P
    from repro.distributed import sharding as SH
    mesh = jax.make_mesh((8,), ("model",), axis_types=(AxisType.Auto,))
    pool_s = SH.serve_pool_specs(CC.init_global_pool(dims, 1))
    cache_s = SH.serve_cache_specs(CC.init_cache(dims), batched=False)
    rep = P()
    return jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(pool_s, rep, cache_s, rep, rep, rep),
        out_specs=(pool_s, rep, cache_s, rep, rep),
        check_vma=False))


def _assert_shards_agree(arr, what):
    """A replicated array must hold byte-identical data on every device
    (catches any cross-shard divergence of the pool accounting)."""
    shards = getattr(arr, "addressable_shards", None)
    if not shards or len(shards) < 2:
        return
    ref = np.asarray(shards[0].data)
    for s in shards[1:]:
        np.testing.assert_array_equal(
            np.asarray(s.data), ref,
            err_msg=f"{what} diverged across shards (device "
                    f"{s.device}) — replicated pool accounting broke")


class _Harness:
    """Host-side mirror of the engine's admit/preempt/resume/share
    bookkeeping at the ct_cache level (no model, no scheduler)."""

    def __init__(self, seed, dims=DIMS, sharded=False):
        self.rng = np.random.default_rng(seed)
        self.dims = dims
        self.sharded = sharded
        self.pool_blocks = _pool_blocks(dims)
        self.pool = CC.init_global_pool(dims, self.pool_blocks)
        self._step = _make_step(dims, sharded)
        if sharded:
            from repro.distributed import sharding as SH
            mesh = jax.make_mesh((8,), ("model",),
                                 axis_types=(AxisType.Auto,))
            self.pool = jax.device_put(
                self.pool,
                SH.to_shardings(SH.serve_pool_specs(self.pool), mesh))
        self.live = {}        # req -> (table, cache)
        self.spilled = {}     # req -> (view, mapped, cache)
        self.cached = []      # prefix-cache-style holders:
        #                       (table np, frozen planes, mapped mask)

    def live_tables(self):
        if not self.live:
            return np.full((1, self.dims.L, self.dims.NB), -1, np.int32)
        return np.stack([np.asarray(t) for t, _ in self.live.values()])

    def check(self):
        CC.check_pool_invariants(self.pool, self.live_tables(),
                                 extra_tables=[t for t, _, _ in self.cached])
        if self.sharded:
            _assert_shards_agree(self.pool.refcount, "pool refcount")
            for r, (t, _) in self.live.items():
                _assert_shards_agree(t, f"request {r} block table")
        # shared-content immutability: every cached holder's planes are
        # bit-identical to the pool content at its mapped blocks
        for table_np, frozen, mapped in self.cached:
            now, _ = CC.extract_request(self.dims, self.pool,
                                        jnp.asarray(table_np))
            for f_p, n_p in zip(frozen, tuple(now)):
                np.testing.assert_array_equal(
                    np.asarray(n_p)[mapped], f_p[mapped],
                    err_msg="shared block content mutated in place "
                            "(COW fault missing)")

    def start(self, r):
        if r in self.live or r in self.spilled:
            return
        self.live[r] = (CC.init_block_table(self.dims),
                        CC.init_cache(self.dims))

    def step(self, r):
        if r not in self.live:
            return
        dims = self.dims
        table, cache = self.live[r]
        k = jnp.asarray(self.rng.standard_normal((dims.L, dims.H, dims.D)),
                        jnp.float32)
        v = jnp.asarray(self.rng.standard_normal((dims.L, dims.H, dims.D)),
                        jnp.float32)
        spars = jnp.float32(self.rng.choice([0.3, 0.65, 0.92]))
        pool, table, cache, _fail, _ncow = self._step(self.pool, table,
                                                      cache, k, v, spars)
        # _fail True is LEGAL here (oversubscribed, no engine headroom
        # logic at this level): claims revert, invariants must still hold
        self.pool, self.live[r] = pool, (table, cache)

    def retire(self, r):
        if r not in self.live:
            return
        table, _ = self.live.pop(r)
        self.pool = CC.release_blocks(self.dims, self.pool, table)

    def preempt(self, r):
        if r not in self.live:
            return
        table, cache = self.live.pop(r)
        view, mapped = CC.extract_request(self.dims, self.pool, table)
        self.spilled[r] = (jax.tree.map(np.asarray, tuple(view)),
                           np.asarray(mapped), cache)
        self.pool = CC.release_blocks(self.dims, self.pool, table)

    def resume(self, r):
        if r not in self.spilled:
            return
        view_np, mapped, cache = self.spilled[r]
        free = np.asarray(self.pool.free).sum(axis=1)
        if (free < mapped.sum(axis=1)).any():
            return               # engine's gate would refuse; stay spilled
        del self.spilled[r]
        view = CC.PoolView(*(jnp.asarray(p) for p in view_np))
        pool, table, ok = CC.restore_request(self.dims, self.pool,
                                             jnp.asarray(mapped), view)
        assert bool(ok), "claim failed despite free-count pre-check"
        self.pool, self.live[r] = pool, (table, cache)
        # restore is bit-exact: re-gathering through the NEW table must
        # reproduce the spilled planes on every mapped block
        back, _ = CC.extract_request(self.dims, self.pool, table)
        for spilled_p, back_p in zip(view_np, tuple(back)):
            sel = mapped
            np.testing.assert_array_equal(
                np.asarray(back_p)[sel], spilled_p[sel])

    def share(self, r):
        """A prefix-cache-style holder increfs r's current mapping and
        pins its content."""
        if r not in self.live:
            return
        table, _ = self.live[r]
        table_np = np.asarray(table).copy()
        if not (table_np >= 0).any():
            return
        self.pool = CC.incref_blocks(self.dims, self.pool,
                                     jnp.asarray(table_np))
        view, mapped = CC.extract_request(self.dims, self.pool,
                                          jnp.asarray(table_np))
        self.cached.append((table_np,
                            jax.tree.map(np.asarray, tuple(view)),
                            np.asarray(mapped)))

    def release_cached(self):
        if not self.cached:
            return
        table_np, _, _ = self.cached.pop(0)
        self.pool = CC.release_blocks(self.dims, self.pool,
                                      jnp.asarray(table_np))

    def cow(self, r):
        """Explicit COW fault over a random subset of r's mapped blocks
        (oversubscribed: the claim may fail — the source must survive)."""
        if r not in self.live:
            return
        dims = self.dims
        table, cache = self.live[r]
        mask = jnp.asarray(self.rng.random((dims.L, dims.NB)) < 0.5)
        pool, table, _ok = CC.cow_blocks(dims, self.pool, table, mask)
        self.pool, self.live[r] = pool, (table, cache)


def _drive(h, ops):
    for r in range(N_REQ):
        h.start(r)
    h.check()
    for op in ops:
        kind, r = divmod(op, N_REQ)
        if kind == 0:
            for _ in range(h.dims.G):     # a full group: guarantees a commit
                h.step(r)
        elif kind == 1:
            h.preempt(r)
        elif kind == 2:
            h.resume(r)
        elif kind == 3:
            h.retire(r)
            h.start(r)                    # fresh request reuses the id
        elif kind == 4:
            h.share(r)
        else:
            h.cow(r) if r % 2 else h.release_cached()
        h.check()
    # drain: retire the live set first (frees their blocks), release the
    # cached holders, then resume + retire the spilled remainder —
    # afterwards the whole pool is free
    for r in range(N_REQ):
        h.retire(r)
    while h.cached:
        h.release_cached()
    for r in range(N_REQ):
        h.resume(r)
        h.retire(r)
    h.check()
    assert not h.spilled
    assert np.asarray(h.pool.free).all(), "drained pool not fully free"


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 2 ** 31 - 1),
       st.lists(st.integers(0, N_KINDS * N_REQ - 1), min_size=14,
                max_size=30))
def test_pool_accounting_invariants_hold(seed, ops):
    _drive(_Harness(seed), ops)


@pytest.mark.skipif(_HAS_MESH_DEVS, reason="outer wrapper; inner run only")
def test_pool_invariants_sharded_subprocess():
    """Re-exec the SHARDED property test with 8 forced host devices."""
    run_in_mesh_subprocess(__file__, extra_args=("-k", "sharded_on_mesh"))


@pytest.mark.skipif(not _HAS_MESH_DEVS,
                    reason="needs 8 forced host devices (re-exec wrapper)")
@settings(max_examples=3, deadline=None)
@given(st.integers(0, 2 ** 31 - 1),
       st.lists(st.integers(0, N_KINDS * N_REQ - 1), min_size=10,
                max_size=18))
def test_pool_accounting_invariants_hold_sharded_on_mesh(seed, ops):
    """The same random-op property on the 8-device mesh, with the step
    inside shard_map and shard-agreement asserted after every op."""
    _drive(_Harness(seed, dims=DIMS8, sharded=True), ops)
