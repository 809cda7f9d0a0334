"""What every plane shares: the client clock, the open-loop sender, the
compile counter, registry deltas, the traced window and the device facts.

Nothing here knows a configuration, a traffic mix or a metric; those come
from files found by name (see ``run.py``).
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import threading
import time
from typing import Callable, Optional

now = time.perf_counter


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# -- compilations ------------------------------------------------------------


class CompileCounter:
    """Counts XLA backend compilations and program lowerings (JAX's own
    monitoring events), so a run can say what was built inside its
    measured window: a lowering without a compilation is a program loaded
    from the persistent cache, which stalls the host all the same."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax

        self.n = 0
        self.lowered: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, fun_name="", **_kw):
        if name == self.COMPILE:
            self.n += 1
        elif name == self.LOWER:
            self.lowered.append(fun_name)


# -- the open loop ------------------------------------------------------------


class Sender(threading.Thread):
    """Submits each arrival at its scheduled time on the client clock,
    whether or not earlier ones have finished. ``submit(arrival) ->
    record`` returns the client's record for the request; the sender adds
    the scheduled and the actual send times to it."""

    def __init__(self, arrivals, t0: float, submit: Callable,
                 records: list, stop: Optional[threading.Event] = None):
        super().__init__(name="bench-sender", daemon=True)
        self.arrivals, self.t0, self.submit = arrivals, t0, submit
        self.records = records
        self.stop_event = stop or threading.Event()

    def run(self):
        for a in self.arrivals:
            due = self.t0 + a.t
            while not self.stop_event.is_set():
                left = due - now()
                if left <= 0:
                    break
                self.stop_event.wait(min(left, 0.05))
            if self.stop_event.is_set():
                return
            rec = {"t_sched": due, "t_sent": now(), "spec": a.spec,
                   "ok": None}
            try:
                rec.update(self.submit(a, rec))
            except Exception as exc:           # refused at submit
                rec["ok"], rec["error"] = False, repr(exc)
            self.records.append(rec)


def pow2_upto(n: int) -> list[int]:
    """1, 2, 4, ... below ``n``, then ``n``: the padded sizes the gateways
    bucket batches and prefill chunks into."""
    out, b = [], 1
    while b < n:
        out.append(b)
        b *= 2
    return out + [n]


def wait_all(records, deadline: float, pred) -> bool:
    while now() < deadline:
        if all(pred(r) for r in records):
            return True
        time.sleep(0.01)
    return all(pred(r) for r in records)


def measure_window(ctx, gateway, submit, compiles, settled,
                   settle_s: float) -> dict:
    """The measured window, the same for every plane: open-loop arrivals
    of the ``window`` stream for ``ctx['seconds']``, then the ``after``
    stream keeps the load on until every window request is ``settled``
    (at most ``settle_s`` past the close); then the sender and the
    gateway's serving thread stop. Registry counters, compilations and
    the trace (``--trace 1``) cover the window exactly."""
    from bench import loadgen

    mix, seed, seconds = ctx["mix"], ctx["seed"], ctx["seconds"]
    c = ctx["model"].c
    arrivals = loadgen.schedule(mix, seed, seconds, "window", c)
    after = loadgen.schedule(mix, seed, settle_s + 60.0, "after", c)
    for a in arrivals:
        a.spec["phase"] = "window"
    for a in after:
        a.t += seconds
    records: list = []
    stop = threading.Event()
    tw = TracedWindow(ctx["trace"])
    snap0 = counters(gateway.metrics.snapshot())
    n0, l0 = compiles.n, len(compiles.lowered)
    tw.start()
    t0 = now()
    sender = Sender(arrivals + after, t0, submit, records, stop)
    sender.start()
    time.sleep(max(0.0, t0 + seconds - now()))
    t1 = now()
    snap1 = counters(gateway.metrics.snapshot())
    n_window = compiles.n - n0
    lowered = compiles.lowered[l0:]
    tw.stop()                        # collecting the trace takes seconds
    while len(records) < len(arrivals) and now() < t1 + settle_s:
        time.sleep(0.01)             # a late sender still owes arrivals
    window = records[:len(arrivals)]
    done = wait_all(window, t1 + settle_s, settled)
    stop.set()
    sender.join()
    gateway.stop()
    late50, late_max = lateness_ms(window)
    log(f"bench: {len(window)} window requests, all settled: {done}; "
        f"{n_window} compilations and {len(lowered)} lowerings inside the "
        f"window {sorted(set(lowered))}; sender late by {late50:.3f} ms "
        f"median, {late_max:.3f} ms worst")
    return {"records": window, "t0": t0, "t1": t1, "window_s": t1 - t0,
            "setup_s": t0 - ctx["t_start"],
            "counters": delta(snap1, snap0), "compiles_in_window": n_window,
            "traced": tw}


def result(ctx, meas: dict, programs: dict, kernels: dict, check) -> dict:
    """Reduce the trace, read the device's memory peak, free the program's
    state (``check`` runs after the caller has dropped its references),
    run the comparison, and assemble what the metric readers get."""
    import gc

    trace = meas.pop("traced").reduce(programs, kernels)
    peak = device_facts(ctx["chips"])["memory_peak_bytes"]
    gc.collect()
    checks = check()
    window = meas["records"]
    out = dict(meas, trace=trace, checks=checks, attempted=len(window),
               failed=sum(1 for r in window if r["ok"] is not True),
               device={"memory_peak_bytes": peak})
    if trace is not None:
        out["device"].update(busy_s=trace["busy_s"],
                             window_s=trace["profile_s"] or meas["window_s"])
        out["breakdown"] = trace["breakdown"]
    return out


def lateness_ms(records) -> tuple[float, float]:
    """Median and worst lateness of the sender (actual - scheduled)."""
    lates = sorted((r["t_sent"] - r["t_sched"]) * 1e3 for r in records)
    if not lates:
        return 0.0, 0.0
    return lates[len(lates) // 2], lates[-1]


# -- registry deltas -----------------------------------------------------------


def counters(snapshot: dict) -> dict:
    """Numbers of a registry snapshot: counters and gauges as they are,
    histograms as their exact ``count`` and ``sum``."""
    out = {}
    for k, v in snapshot.items():
        if k == "_meta":
            continue
        if isinstance(v, dict):
            out[k + ".count"] = v.get("count", 0)
            out[k + ".sum"] = v.get("sum", 0.0)
        elif isinstance(v, (int, float)):
            out[k] = v
    return out


def delta(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()}


# -- the traced window ----------------------------------------------------------


class TracedWindow:
    """JAX profiler around the measured window (``--trace 1`` only); the
    trace lives in a temporary directory under ``TMPDIR`` and is reduced
    and deleted before the run ends."""

    def __init__(self, on: bool):
        self.on = on
        self.dir = None

    def start(self):
        if self.on:
            import jax

            self.dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # spans and device only
            jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self):
        if self.on:
            import jax

            jax.profiler.stop_trace()

    def reduce(self, programs: dict, kernels: dict):
        """``trace.reduce`` of the file, then the directory is removed."""
        if not self.on:
            return None
        from bench.trace import reduce

        try:
            path = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                             recursive=True)[0]
            return reduce(path, programs, kernels)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


# -- device --------------------------------------------------------------------


def device_facts(chips: int) -> dict:
    import jax

    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def percentile(values, q: float) -> Optional[float]:
    """Linear-interpolated percentile of ``values`` (None when empty)."""
    import numpy as np

    v = np.asarray(sorted(values), float)
    if v.size == 0:
        return None
    return float(np.percentile(v, q, method="linear"))
