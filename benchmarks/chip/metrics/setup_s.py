"""Set-up seconds: process start (imports included) to the window's
opening: weights, warm-up and, where the cache misses, compilation."""


def read(run):
    return run.setup_s
