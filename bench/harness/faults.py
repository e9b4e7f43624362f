"""Faults planted under the timed path, for the benchmark's own tests:
each must turn ``correct`` false.  Each wraps the engine's decode tick
(``ThinKVEngine._tick``) from the window's open, when every cache is at
its budget.

* ``state_unchanged``: the tick hands back the pool, block tables and
  cache metadata it was given, so no decode step ever lands in the cache;
* ``half_batch``: the odd slots' tokens are not computed but copied from
  the even slot before them;
* ``token_altered``: every sampled token is shifted by one id.

One chip has no exchange between chips to leave out.
"""
from __future__ import annotations


def _wrap(eng, post):
    tick = eng._tick

    def broken(params, pool, tables, caches, *rest):
        out = tick(params, pool, tables, caches, *rest)
        return post(out, (pool, tables, caches))
    eng._tick = broken


def state_unchanged(eng) -> None:
    _wrap(eng, lambda out, given: (out[0], *given, *out[4:]))


def half_batch(eng) -> None:
    def post(out, _):
        nxt = out[0]
        return (nxt.at[1::2].set(nxt[0::2]), *out[1:])
    _wrap(eng, post)


def token_altered(eng) -> None:
    V = eng.mcfg.vocab_size
    _wrap(eng, lambda out, _: ((out[0] + 1) % V, *out[1:]))


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "token_altered": token_altered}
