"""Device idle time per decode tick while the serve loop dispatched: the
part of the window in which no operation ran on the device and the
program's ``serve/dispatch`` span (commit headroom, input transfers and
the tick's launch) was open, over the tick program's executions."""
from harness import scopes


def read(run):
    tr = scopes.names()
    if run.traced is None or tr is None:
        return None
    return scopes.idle_ms_per_tick(run.traced.events, tr.DISPATCH)
