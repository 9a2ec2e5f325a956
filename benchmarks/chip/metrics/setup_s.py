"""Set-up seconds: process start to the window's start (import, weights, schedule search, pool, compile or cache load, warm-up)."""


def read(run):
    return run.setup_s
