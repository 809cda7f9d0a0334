"""Reduce a JAX profiler trace (``*.xplane.pb``) to the numbers the
per-layer readers use.

Planes named ``/device:TPU:<n>`` hold the device's timelines: the ``XLA
Ops`` line has one event per operation run on the chip, named by its HLO
text (``%fusion.108 = bf16[...] fusion(...)``; a ``while`` loop's event
spans the operations of its body, which have events of their own), the
``XLA Modules`` line one per program execution, named after the jitted
function (``jit__extend(<fingerprint>)``). The host plane holds the
program's own ``profile_span`` annotations (``continuous.leg.4-8``,
``decode.step.k32``) on the thread that dispatched them, and the ``Task
Environment`` plane the profile's start and stop. Every plane is on one
clock, so an idle gap on the device can be laid against what the host was
doing meanwhile.

``reduce`` returns, all in seconds:
  * ``busy_s``: the union of the operations' intervals, averaged over the
    devices that ran any;
  * ``programs``: device time of the program executions whose names start
    with each family's prefixes, and ``program_calls`` their counts;
  * ``kernels``: device time of the operations whose names contain each
    kernel's name, split by the program family that ran them;
  * ``spans``: each host span of the program (name, start, end);
  * ``gaps``: the ten longest idle intervals between device operations,
    each keyed by the innermost host span covering its middle, or by the
    last span started before it;
  * ``breakdown``: the ten kinds of operation (``op_kind``) with the most
    device time and the ten longest idle gaps, for the result line.
"""
from __future__ import annotations

from typing import Iterable

SPAN_PREFIXES = ("continuous.", "decode.", "gateway.")
# operations whose events enclose the events of the operations they run
CONTAINERS = ("%while", "%conditional", "%call")


def op_kind(hlo: str) -> str:
    """What an operation is, without the number that makes each
    instruction unique: ``%fusion.108 = bf16[...] fusion(...),
    kind=kOutput, ...`` -> ``fusion:kOutput``; ``%paged_attention.9 =
    ... custom-call(...)`` -> ``paged_attention``; ``%copy.82`` ->
    ``copy``."""
    name = hlo.split(" = ", 1)[0].lstrip("%")
    base = name.rstrip("0123456789").rstrip(".")
    if "fusion" in base and "kind=" in hlo:
        return base + ":" + hlo.split("kind=", 1)[1].split(",", 1)[0]
    return base


def _merge(intervals: Iterable[tuple[int, int]]) -> list[list[int]]:
    out: list[list[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def reduce(path: str, programs: dict, kernels: dict, data=None) -> dict:
    """``programs`` maps a family to program-name prefixes, ``kernels`` a
    kernel to substrings of its operation names. ``data`` may be given
    instead of ``path`` (a ``ProfileData`` or a test double with the same
    ``planes``/``lines``/``events`` shape)."""
    pd = data if data is not None else load(path)
    busy_per_dev, ops_time = [], {}
    prog_time = {f: 0.0 for f in programs}
    prog_calls = {f: 0 for f in programs}
    kern_time = {k: {} for k in kernels}
    modules: list[tuple[int, int, str]] = []
    op_ivals: list[tuple[int, int]] = []
    spans: list[tuple[str, int, int]] = []
    profiled = None
    for plane in pd.planes:
        if is_device_plane(plane.name):
            dev_ops, dev_mods = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for e in line.events:
                        dev_mods.append((e.start_ns, e.start_ns + e.duration_ns,
                                         e.name))
                elif line.name == "XLA Ops":
                    for e in line.events:
                        dev_ops.append((e.start_ns, e.start_ns + e.duration_ns,
                                        e.name))
            if not dev_ops:
                continue
            merged = _merge((a, b) for a, b, _ in dev_ops)
            busy_per_dev.append(sum(b - a for a, b in merged))
            op_ivals.extend((a, b) for a, b in merged)
            for a, b, name in dev_ops:
                if not name.startswith(CONTAINERS):
                    kind = op_kind(name)
                    ops_time[kind] = ops_time.get(kind, 0) + (b - a)
            dev_mods.sort()
            for a, b, name in dev_mods:
                fam = _family(name, programs)
                if fam is not None:
                    prog_time[fam] += (b - a) / 1e9
                    prog_calls[fam] += 1
            modules.extend(dev_mods)
            for a, b, name in dev_ops:
                for k, subs in kernels.items():
                    if any(s in name for s in subs):
                        fam = _family(_enclosing(dev_mods, a), programs)
                        kern_time[k][fam] = kern_time[k].get(fam, 0.0) \
                            + (b - a) / 1e9
        elif plane.name == "Task Environment":
            st = dict(plane.stats)
            if "profile_start_time" in st and "profile_stop_time" in st:
                profiled = (int(st["profile_stop_time"])
                           - int(st["profile_start_time"])) / 1e9
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    merged = _merge(op_ivals)
    idle = sorted(((a2 - b, b, a2) for (_, b), (a2, _)
                   in zip(merged, merged[1:])), reverse=True)[:10]
    gaps = [(_innermost(spans, (b + a2) // 2), d / 1e9) for d, b, a2 in idle]
    top_ops = sorted(ops_time.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": (sum(busy_per_dev) / len(busy_per_dev) / 1e9
                   if busy_per_dev else 0.0),
        "devices": len(busy_per_dev),
        "profile_s": profiled,
        "programs": prog_time,
        "program_calls": prog_calls,
        "kernels": kern_time,
        "spans": [(n, a / 1e9, b / 1e9) for n, a, b in spans],
        "gaps": gaps,
        "breakdown": {
            "device_ops": [[n, t / 1e9] for n, t in top_ops],
            "idle_gaps": [[n, s] for n, s in gaps],
        },
    }


def _family(name, programs: dict):
    if name is None:
        return None
    for fam, prefixes in programs.items():
        if any(name.startswith(p) for p in prefixes):
            return fam
    return None


def _enclosing(mods: list, t: int):
    """Name of the program execution (sorted by start) running at ``t``."""
    lo, hi = 0, len(mods)
    while lo < hi:
        mid = (lo + hi) // 2
        if mods[mid][0] <= t:
            lo = mid + 1
        else:
            hi = mid
    if lo and mods[lo - 1][0] <= t < mods[lo - 1][1]:
        return mods[lo - 1][2]
    return None


def _innermost(spans: list, t: int) -> str:
    """The innermost host span covering ``t``; else the last one the host
    had started before it (``after <span>``: the device waits on what the
    host does after that dispatch)."""
    best = last = None
    for name, a, b in spans:
        if a <= t < b and (best is None or b - a < best[1]):
            best = (name, b - a)
        if a <= t and (last is None or a > last[1]):
            last = (name, a)
    if best:
        return best[0]
    return "after " + last[0] if last else "before any span"
