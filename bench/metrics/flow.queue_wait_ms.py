"""flow.queue_wait_ms: mean queue wait, submit to admission, of the
requests the gateway settled in the window: the registry's exact
``wait_ms`` sum over its count (its percentiles come from coarse
buckets and are not used)."""


def read(run):
    n = run["counters"].get("wait_ms.count", 0)
    return run["counters"]["wait_ms.sum"] / n if n else None
