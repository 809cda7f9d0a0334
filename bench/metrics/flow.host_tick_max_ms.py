"""flow.host_tick_max_ms: the longest self time of one ``gateway.pump``
span in the profile (its length less its ``*.sync.*`` children;
``bench.spans``), in ms. A stall of the serving thread reads here; when
this reads small while the sender was late, the stall was elsewhere.
None when the trace holds no pump span."""
from bench.spans import pump_self_times


def read(run):
    tr = run["trace"]
    if tr is None:
        return None
    ticks = pump_self_times(tr["spans"])
    return 1e3 * max(ticks) if ticks else None
