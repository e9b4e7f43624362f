"""Device time of cache maintenance per decode tick: the union of the
intervals of the tick's operations under the ``advance`` scope (the
``engine_advance`` scan over the slots: group commit and eviction,
thought refresh, block-table sync), clipped to the window and to the
tick program's executions, over their number."""
from harness import scopes


def read(run):
    tr = scopes.names()
    if run.traced is None or tr is None:
        return None
    return scopes.scope_ms_per_tick(run.traced.events, tr.ADVANCE)
