"""What the metric readers share: the window's client records, the
registry deltas and the reduced trace of one run, read the same way by
every reader.

A reader returns None when the run holds nothing for it to read (no
trace, no device time of its program, no request of its kind); the
harness then leaves the metric out of the result line.
"""
from __future__ import annotations

from bench.harness import percentile

__all__ = ["percentile", "share", "in_window", "flow_row_steps"]


def share(least_s: float, device_s: float):
    """Least time over device time, in percent (None without device
    time)."""
    if not device_s:
        return None
    return 100.0 * least_s / device_s


def in_window(run: dict, t: float) -> bool:
    return run["t0"] <= t <= run["t1"]


def flow_row_steps(run: dict) -> float:
    """NFE steps of one request row each that the window's requests got
    inside the window. A request needs exactly its served budget's steps
    of its own row, whether it rode a trajectory, joined one through a
    prefix or went to a flush batch; its steps are spread evenly from its
    admission (its send plus the queue wait its response reports) to its
    latents' arrival, and the part inside the window counts. Padded rows
    never count: the work is what the requests need, not what was
    dispatched."""
    total = 0.0
    for r in run["records"]:
        if not r.get("ok"):
            continue
        a, d = r["t_admit"], r["t_done"]
        inside = min(d, run["t1"]) - max(a, run["t0"])
        if inside > 0:
            total += r["served"] * inside / (d - a)
    return total
