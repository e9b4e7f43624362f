"""Device time of the dense sparsity probe per decode tick: the union of
the intervals of the tick's operations under the ``sparsity_probe`` scope
(the ``lax.cond`` that runs the dense attention pass of the calibrated
layers on ticks where some slot refreshes, both branches), clipped to the
window and to the tick program's executions, over their number (every
tick, probing or not)."""
from harness import scopes


def read(run):
    tr = scopes.names()
    if run.traced is None or tr is None:
        return None
    return scopes.scope_ms_per_tick(run.traced.events, tr.PROBE)
