"""Share of slot-ticks that produced a token in the window: the engine's
token count over its tick count times the slots (host counters)."""


def read(run):
    ticks = run.counter("ticks")
    if ticks <= 0:
        return None
    return 100.0 * run.counter("tokens") / (ticks * run.dims["max_seqs"])
