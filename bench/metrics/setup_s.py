"""Set-up: process start to the first timed call, compilation, weights,
plan freezing and the warm-up wave or product included (host clock)."""


def read(run):
    return run.setup_s if run.window_s > 0 else None
