"""Share of the traced window in which no operation ran on the device."""
from harness import trace


def read(run):
    if run.traced is None:
        return None
    ev = run.traced.events
    lo, hi = trace.window(ev)
    return 100.0 * (1.0 - trace.busy_seconds(ev) / ((hi - lo) / 1e9))
