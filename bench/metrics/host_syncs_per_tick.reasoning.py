"""Blocking device-to-host reads the engine made outside the tick's
result fetch, per decode tick, in the traced window: the engine's
``host_syncs`` counter over its ``ticks`` counter."""


def read(run):
    t = run.traced
    if t is None or "host_syncs" not in t.counters_close:
        return None
    ticks = t.counter("ticks")
    return t.counter("host_syncs") / ticks if ticks > 0 else None
