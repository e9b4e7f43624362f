"""95th percentile of every gap between consecutive delivered tokens of
one request, over all requests, whose later token lands in the window."""
from harness import timeline


def read(run):
    v = timeline.p95(timeline.token_gaps(run.records, run.open, run.close))
    return None if v is None else v * 1e3
