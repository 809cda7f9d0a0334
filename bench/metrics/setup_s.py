"""setup_s: process start to the window's first request (host clock):
loading, weights made from the seed, warm-up, compilation where the
persistent cache misses."""


def read(run):
    return run["setup_s"]
