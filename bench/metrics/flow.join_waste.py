"""flow.join_waste: backbone forwards spent on join prefixes (a joiner's
steps 0..k recomputed from its own noise) over all forwards dispatched in
the window, in percent (registry ``join_forwards`` / ``forwards``)."""


def read(run):
    f = run["counters"].get("forwards", 0)
    return 100.0 * run["counters"].get("join_forwards", 0) / f if f else None
