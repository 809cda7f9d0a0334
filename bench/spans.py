"""What the serving thread's profiler spans say, read the same way by
every reader of them.

The program names each phase of its serving thread in a span on the
profiler's clock: ``gateway.pump`` is one whole tick, the parent of the
others; a ``*.sync.*`` span inside it is the thread blocked on a device
readback; ``gateway.idle`` is the poll sleep between ticks. The reduced
trace (``bench.trace``) carries each span as ``(name, start_s, end_s)``.
"""
from __future__ import annotations

PUMP = "gateway.pump"
SYNC = ".sync."


def pump_self_times(spans) -> list[float]:
    """Self time, in seconds, of each ``gateway.pump`` span: its length
    less the part its ``*.sync.*`` children cover. That is the host's own
    work in the tick; the waits on device results are left out. Empty
    when the trace holds no pump span (a program that does not name its
    ticks)."""
    pumps = sorted((a, b) for n, a, b in spans if n == PUMP)
    syncs = sorted((a, b) for n, a, b in spans if SYNC in n)
    out, j = [], 0
    for a, b in pumps:
        while j < len(syncs) and syncs[j][1] <= a:
            j += 1
        covered, k = 0.0, j
        while k < len(syncs) and syncs[k][0] < b:
            covered += max(0.0, min(b, syncs[k][1]) - max(a, syncs[k][0]))
            k += 1
        out.append(b - a - covered)
    return out
