"""Output tokens delivered to clients in the window, per second."""
from harness import timeline


def read(run):
    return timeline.token_rate(run.records, run.open, run.close)
