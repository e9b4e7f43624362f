"""Device time of one decode tick: the tick program's executions in the
trace, summed, over their number."""
from harness.context import TICK_PROGRAM as TICK


def read(run):
    if run.traced is None:
        return None
    n = run.module_count(TICK)
    return run.module_seconds(TICK) * 1e3 / n if n else None
