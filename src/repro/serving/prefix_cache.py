"""Copy-on-write prefix cache over the shared :class:`GlobalPool`.

Shared-prompt fleets (one system prompt / few-shot preamble across
thousands of requests) dominate the "millions of users" traffic shape the
ROADMAP targets, yet without reuse every request pays FULL prefill
compute and private physical blocks for a byte-identical prefix.
Prefill-committed blocks are a deterministic function of (params, token
prefix, ThinKV config) — the TBQ quantization, CT slot placement, TBE
eviction, and thought refreshes inside prefill depend on nothing else —
so they are SHAREABLE until some holder's later commit mutates them, at
which point the refcounted pool's copy-on-write fault (see
``core.ct_cache.sync_block_tables``) gives the writer a private copy and
leaves the cached planes pristine.

The cache is a host-side token-chain index over FULLY-COMMITTED prefill
states:

* **key** — the byte string of the first ``n`` prompt tokens, registered
  at commit-aligned chunk boundaries during prefill (``n % g == 0``, TBQ
  buffer empty) and once at end-of-prompt (possibly with a partial
  buffer — such entries are ``full_only``: usable only when the new
  prompt matches the key EXACTLY, since chunked prefill cannot resume on
  an unaligned buffer).
* **value** — the per-layer block table at that boundary (logical →
  physical mapping of the committed blocks), a numpy snapshot of the
  request's ``CTCache`` metadata pytree (slot states/bits/segments, TBQ
  buffer, thought bookkeeping, and the window layers' rings, which at a
  boundary hold keys and values of the prefix alone), and the boundary's
  last-token logits (so an exact full-prompt hit needs no forward pass at
  all).

Registration INCREFS every mapped block (the cache is a first-class
reference holder); a hit increfs them again for the admitted request and
restores the metadata snapshot, so the request skips every covered
prefill chunk and prefills only the tail.  Entries are evicted in LRU
order under pool pressure — the engine decays the cache BEFORE preempting
any running request, since dropping a cache reference can free blocks
without pausing work (blocks still mapped by running or preempted
requests merely decref and stay live).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ct_cache as CC


@dataclasses.dataclass
class PrefixEntry:
    """One cached prefix: everything needed to resume prefill after it."""

    key: bytes                 # prompt[:length] int32 bytes
    length: int                # tokens covered (commit boundary)
    table: np.ndarray          # [L, NB] int32 physical mapping (-1 unmapped)
    cache: object              # CTCache snapshot with numpy leaves
    logits: np.ndarray         # last covered token's logits [V]
    full_only: bool            # nonzero TBQ buffer: exact-match only
    last_used: int = 0         # LRU stamp

    @property
    def blocks_per_layer(self) -> np.ndarray:
        return (self.table >= 0).sum(axis=1).astype(np.int64)


class PrefixCache:
    """Host-side LRU index of shareable prefill prefixes.

    All pool mutations go through the refcount ops and are returned to
    the caller (the engine owns the authoritative ``GlobalPool``)."""

    def __init__(self, dims: CC.CacheDims, capacity: int = 64):
        self.dims = dims
        self.capacity = max(int(capacity), 1)
        self.entries: Dict[bytes, PrefixEntry] = {}
        self._clock = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    # ------------------------------------------------------------------
    def _touch(self, entry: PrefixEntry) -> None:
        self._clock += 1
        entry.last_used = self._clock

    @staticmethod
    def _key(prompt: np.ndarray, n: int) -> bytes:
        return np.ascontiguousarray(prompt[:n], np.int32).tobytes()

    def lookup(self, prompt: np.ndarray, record: bool = True
               ) -> Optional[PrefixEntry]:
        """Longest registered prefix of ``prompt`` (None on miss).

        ``full_only`` entries (partial TBQ buffer) match only when the
        entry covers the ENTIRE prompt; boundary entries (empty buffer)
        may cover any commit-aligned proper prefix.  A hit ALWAYS
        freshens the entry's LRU stamp — a probing lookup (the engine's
        admission gate shrinking its watermark estimate, ``record=False``
        to keep it out of the hit/miss stats) must pin the entry it
        relied on so pressure-driven decay evicts it last, not first.
        """
        best = None
        for n in sorted({e.length for e in self.entries.values()},
                        reverse=True):
            if n > len(prompt):
                continue
            e = self.entries.get(self._key(prompt, n))
            if e is None or (e.full_only and n != len(prompt)):
                continue
            best = e
            break
        if best is not None:
            self._touch(best)
        if record:
            if best is None:
                self.misses += 1
            else:
                self.hits += 1
        return best

    # ------------------------------------------------------------------
    def register(self, pool: CC.GlobalPool, prompt: np.ndarray, n: int,
                 table, cache, logits, full_only: bool) -> CC.GlobalPool:
        """Index the committed prefill state at boundary ``n`` and incref
        its mapped blocks (skips boundaries already registered)."""
        key = self._key(prompt, n)
        if key in self.entries:
            self._touch(self.entries[key])
            return pool
        while self.entries and len(self.entries) >= self.capacity:
            pool, _ = self.evict_lru(pool)
        entry = PrefixEntry(
            key=key, length=int(n), table=np.asarray(table).copy(),
            cache=jax.tree.map(lambda x: np.asarray(x).copy(), cache),
            logits=np.asarray(logits).copy(), full_only=bool(full_only))
        self._touch(entry)
        self.entries[key] = entry
        return CC.incref_blocks(self.dims, pool, jnp.asarray(entry.table))

    def evict_entry(self, pool: CC.GlobalPool, entry: PrefixEntry
                    ) -> CC.GlobalPool:
        """Drop a specific entry, decrefing its blocks (blocks still
        mapped by requests stay live)."""
        del self.entries[entry.key]
        self.evictions += 1
        return CC.release_blocks(self.dims, pool, jnp.asarray(entry.table))

    def evict_lru(self, pool: CC.GlobalPool):
        """Drop the least-recently-used entry.  Returns
        ``(pool, entry_or_None)``."""
        if not self.entries:
            return pool, None
        entry = min(self.entries.values(), key=lambda e: e.last_used)
        return self.evict_entry(pool, entry), entry

    def lru_entries(self) -> List[PrefixEntry]:
        """Entries in LRU-first order (the decay scan order)."""
        return sorted(self.entries.values(), key=lambda e: e.last_used)

    def drop_all(self, pool: CC.GlobalPool) -> CC.GlobalPool:
        while self.entries:
            pool, _ = self.evict_lru(pool)
        return pool

    # ------------------------------------------------------------------
    def cached_tables(self) -> List[np.ndarray]:
        """One ``[L, NB]`` table per entry (each registration holds one
        reference per mapped block) — for pool-invariant audits."""
        return [e.table for e in self.entries.values()]

    def stats(self) -> Dict[str, int]:
        total = self.hits + self.misses
        return {"entries": len(self.entries), "hits": self.hits,
                "misses": self.misses, "evictions": self.evictions,
                "hit_rate": self.hits / total if total else 0.0}
