"""Process start to window open: imports, weights, engine, compile-cache
loads, warm-up and priming."""


def read(run):
    return run.setup_s
