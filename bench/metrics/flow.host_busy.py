"""flow.host_busy: the serving thread's own work over the profiled
interval, in percent: the self time of every ``gateway.pump`` span (its
length less its ``*.sync.*`` children, the waits on device results;
``bench.spans``) summed, over ``profile_s``. Where that work is not
overlapped by device work already queued, it is device idle. None when
the trace holds no pump span."""
from bench.spans import pump_self_times


def read(run):
    tr = run["trace"]
    if tr is None or not tr["profile_s"]:
        return None
    ticks = pump_self_times(tr["spans"])
    if not ticks:
        return None
    return 100.0 * sum(ticks) / tr["profile_s"]
