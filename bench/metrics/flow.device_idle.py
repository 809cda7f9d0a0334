"""flow.device_idle: share of the profiled interval, which spans the
window, in which no operation ran on the device (1 - busy / profiled),
in percent."""


def read(run):
    tr = run["trace"]
    if tr is None or not tr["busy_s"] or not tr["profile_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["profile_s"])
