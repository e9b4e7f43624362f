"""Device idle time per decode tick while the serve loop waited for the
tick's result: the part of the window in which no operation ran on the
device and the program's ``serve/wait`` span (the tokens, flags and
logits coming to the host) was open, over the tick program's
executions."""
from harness import scopes


def read(run):
    tr = scopes.names()
    if run.traced is None or tr is None:
        return None
    return scopes.idle_ms_per_tick(run.traced.events, tr.WAIT)
