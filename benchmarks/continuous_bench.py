"""Continuous batching vs flush-only: tail latency and NFE per request.

Event-driven simulation over the analytic toy field with a FAKE clock —
time advances by (backbone forwards spent) x ``--step-ms``, so the
measurement is fully deterministic (no wall-clock, no compile noise, no
machine variance: CI compares these numbers against committed baselines at
tight tolerance). Both gateways see the identical arrival schedule of
single-sample requests at mixed NFE budgets.

What the flush-only gateway cannot do: a request arriving one tick after a
flush waits out ``max_wait_ms`` (or a full bucket) while a long in-flight
dispatch holds the device. The continuous gateway admits it into the
in-flight anytime trajectory at the next exit boundary — its wait ends at
admission, and its prefix costs only the boundary it joins at.

Measurement is conservative for the baseline: the flush gateway plans every
ready batch at the same instant before the simulated execution time
elapses, so its recorded waits UNDERSTATE what a real serial device would
show; the continuous gateway pays its leg-by-leg schedule in full.

Acceptance (ISSUE 4): on the mixed-budget workload, p95 wait >= 1.5x lower
than flush-only with no more total backbone forwards. ``--check`` exits
non-zero when a claim FAILs; ``--json out.json`` writes the summary +
regression metrics CI publishes and gates on.

The MULTIMODAL scenario (ISSUE 10) drives the three proxy workloads'
native request lengths — taxonomy text (5/7/8 rows), audio infill
(10/13/16), image latents (16) — through ONE ContinuousGateway twice over
the identical arrival schedule: once grouping by exact shape (six
fragmented groups, ``tiers=None``) and once under a two-rung
``ShapeLadder`` (text on the short rung, audio + image sharing the long
one). Acceptance: the tiered pool reaches strictly higher slot occupancy
at no more total forwards, and every tiered sample is bit-identical to
the direct sampler at its native shape (padding cropped on settle).
"""
from __future__ import annotations

import argparse
import json
import re
from collections import deque

import jax
import numpy as np

from repro.observability import bucket_bounds_at
from repro.serving import ContinuousGateway, Gateway, Request, ShapeLadder
from repro.serving.toy import FakeClock, ToyAnytimeSampler

try:                                    # via run.py (repo root on sys.path)
    from benchmarks.audio_proxy import REQUEST_LENGTHS as AUDIO_LENGTHS
    from benchmarks.t2i_proxy import REQUEST_LENGTHS as IMAGE_LENGTHS
    from benchmarks.taxonomy_bench import REQUEST_LENGTHS as TEXT_LENGTHS
except ImportError:                     # run directly as a script
    from audio_proxy import REQUEST_LENGTHS as AUDIO_LENGTHS
    from t2i_proxy import REQUEST_LENGTHS as IMAGE_LENGTHS
    from taxonomy_bench import REQUEST_LENGTHS as TEXT_LENGTHS

BUDGETS = (4, 8, 16)
# multimodal tier ladder: text rides the short rung, audio + image share
# the long one — six native lengths collapse onto two slot pools
TIER_RUNGS = (8, 16)
MODALITIES = (("text", TEXT_LENGTHS), ("audio", AUDIO_LENGTHS),
              ("image", IMAGE_LENGTHS))


class ToyCarrySampler(ToyAnytimeSampler):
    """Eager shared toy sampler whose every batch-level velocity evaluation
    ticks the fake clock by ``step_ms``, so queue waits accumulate through
    simulated EXECUTION — a request arriving while a long dispatch runs
    pays for it, under either gateway. The simulation meters forwards, not
    wall time, so nothing is jitted."""

    def __init__(self, budgets=BUDGETS, seed=0, jitter=0.1):
        super().__init__(budgets=budgets, seed=seed, jitter=jitter,
                         jit=False)
        self.tick = None          # set by the simulator

    def on_forward(self):
        if self.tick is not None:
            self.tick()


MIXES = {
    # the headline workload: all three budgets interleaved, so flush-only
    # either fragments into per-budget partials or waits out max_wait
    "mixed": lambda i: BUDGETS[i % len(BUDGETS)],
    # top-heavy: most requests ride long trajectories, joiners everywhere
    "skew16": lambda i: 16 if i % 4 else 4,
}


def schedule(mix: str, requests: int, inter_ms: float,
             burst: int) -> list[tuple[float, int, int]]:
    """Deterministic arrivals: an opening burst (fills the first trajectory
    or bucket) then a steady stream — (arrive_s, budget, request_id)."""
    budget_of = MIXES[mix]
    events = []
    for i in range(requests):
        t_ms = 0.0 if i < burst else (i - burst + 1) * inter_ms
        events.append((t_ms / 1e3, budget_of(i), i))
    return events


def schedule_multimodal(requests: int, inter_ms: float, burst: int):
    """Interleaved multi-modal arrivals: modalities round-robin and each
    cycles its proxy workload's native REQUEST_LENGTHS, budgets cycling
    the grid — (arrive_s, budget, request_id, rows). The stream mixes six
    distinct x0 shapes, so exact-shape grouping fragments while a
    two-rung ladder keeps two pools full."""
    events = []
    for i in range(requests):
        _, lengths = MODALITIES[i % len(MODALITIES)]
        rows = lengths[(i // len(MODALITIES)) % len(lengths)]
        t_ms = 0.0 if i < burst else (i - burst + 1) * inter_ms
        events.append((t_ms / 1e3, BUDGETS[i % len(BUDGETS)], i, rows))
    return events


def simulate(make_gateway, events, step_ms: float):
    """Drive one gateway through the arrival schedule. Execution advances
    the clock from INSIDE the sampler (one tick per batch-level forward),
    so a dispatch's cost is on the clock before the next plan runs; the
    loop only hops time when the gateway is idle (to the next arrival, or
    in small steps to age out partial batches)."""
    clock = FakeClock()
    sampler = ToyCarrySampler()
    gw = make_gateway(sampler, clock)
    pending = deque(events)
    futures = []

    def submit_due():
        while pending and pending[0][0] <= clock.t + 1e-12:
            ev = pending.popleft()
            budget, i = ev[1], ev[2]
            # multimodal events carry a native row count: x0 is (rows, 2)
            shape = (ev[3], 2) if len(ev) > 3 else (2,)
            x0 = jax.random.normal(jax.random.PRNGKey(1000 + i), shape)
            futures.append(gw.submit(Request(budget=budget, x0=x0)))

    def tick():
        # clients are asynchronous: arrivals land DURING a dispatch (submit
        # is thread-safe and lock-free wrt planning), so a request due
        # mid-leg is visible to the very next boundary's join plan — for
        # the flush gateway, to the very next batch plan
        clock.advance(step_ms / 1e3)
        submit_due()

    sampler.tick = tick
    idle_hop = min(step_ms, gw.scheduler.max_wait_s * 1e3) / 2e3
    while pending or gw.queue.depth() or getattr(gw, "_traj", None):
        submit_due()
        if gw.pump() == 0:
            if pending and pending[0][0] > clock.t:
                clock.advance(pending[0][0] - clock.t)   # hop to next arrival
            else:
                clock.advance(idle_hop)                  # age the stragglers
    resps = [f.result() for f in futures]
    waits = np.array([r.meta["wait_ms"] for r in resps])
    return waits, gw.stats(), gw.metrics.snapshot(), resps


def run(requests: int = 96, max_slots: int = 8, step_ms: float = 2.0,
        max_wait_ms: float = 12.0, inter_ms: float = 6.0, max_leg: int = 4,
        log=print, registry_out=None):
    """Moderate steady load (service keeps up with arrivals; buckets do NOT
    fill before ``max_wait_ms``): the regime continuous batching targets —
    flush-only ages out partial batches while requests that could join an
    in-flight trajectory sit in the queue. At saturation both gateways
    degenerate to full buckets and the gap closes (skew16 shows flush-only
    already near-optimal when one budget dominates)."""
    rows = []
    for mix in MIXES:
        events = schedule(mix, requests, inter_ms, burst=max_slots)
        flush_waits, flush_stats, flush_snap, _ = simulate(
            lambda sampler, clock: Gateway(sampler, max_batch=max_slots,
                                           max_wait_ms=max_wait_ms,
                                           clock=clock),
            events, step_ms)
        cont_waits, cont_stats, cont_snap, _ = simulate(
            lambda sampler, clock: ContinuousGateway(
                sampler, max_slots=max_slots, max_wait_ms=max_wait_ms,
                clock=clock, max_leg=max_leg),
            events, step_ms)
        if registry_out is not None:
            registry_out[mix] = {"flush": flush_snap, "cont": cont_snap}
        # the registry's interpolated p95 must agree with the exact
        # per-request percentile to within one histogram bucket width
        hist = cont_snap["wait_ms"]
        lo, hi = bucket_bounds_at(hist["bounds"], hist["buckets"], 95.0)
        width = float(hi - lo) if np.isfinite(hi) else float("inf")
        row = {
            "mix": mix,
            "requests": requests,
            "max_slots": max_slots,
            "step_ms": step_ms,
            "flush_p95_wait_ms": float(np.percentile(flush_waits, 95)),
            "cont_p95_wait_ms": float(np.percentile(cont_waits, 95)),
            "flush_mean_wait_ms": float(flush_waits.mean()),
            "cont_mean_wait_ms": float(cont_waits.mean()),
            "p95_ratio": float(np.percentile(flush_waits, 95)
                               / max(np.percentile(cont_waits, 95), 1e-9)),
            "flush_forwards": flush_stats["forwards"],
            "cont_forwards": cont_stats["forwards"],
            "forwards_ratio": cont_stats["forwards"]
            / max(flush_stats["forwards"], 1),
            "flush_nfe_per_request": flush_stats["nfe_per_request"],
            "cont_nfe_per_request": cont_stats["nfe_per_request"],
            "joins": cont_stats["joins"],
            "join_rate": cont_stats["join_rate"],
            "trajectories": cont_stats["trajectories"],
            "slot_occupancy": cont_stats["slot_occupancy"],
            "cont_p95_wait_ms_registry": float(hist["p95"]),
            "registry_p95_bucket_width": width,
            "registry_p95_delta": float(
                abs(hist["p95"] - np.percentile(cont_waits, 95))),
            "wait_hist_count": int(hist["count"]),
        }
        rows.append(row)
        log(f"{mix}: p95 wait {row['flush_p95_wait_ms']:.1f}ms (flush) -> "
            f"{row['cont_p95_wait_ms']:.1f}ms (continuous, "
            f"{row['p95_ratio']:.1f}x better); forwards "
            f"{row['flush_forwards']} -> {row['cont_forwards']} "
            f"({row['joins']} joins, join_rate {row['join_rate']:.2f}, "
            f"slot_occupancy {row['slot_occupancy']:.2f})")
    rows.append(run_multimodal(requests=requests, max_slots=max_slots,
                               step_ms=step_ms, max_wait_ms=max_wait_ms,
                               inter_ms=inter_ms, max_leg=max_leg, log=log,
                               registry_out=registry_out))
    return rows


_LEG_DISPATCHES = re.compile(r'dispatches\{program="leg/(\d+)-(\d+)-k\d+"\}')


def slot_fill(snap, max_slots: int) -> float:
    """Live slot-steps over all ``max_slots`` slots of every leg step: how
    full a gateway's trajectories run, which shape tiers raise by packing
    more requests into each. The gateway's own ``slot_occupancy`` divides
    by the slot-steps it dispatched, and a leg dispatches only its live
    slots' power of two, so that ratio no longer sees the packing."""
    steps = 0
    for key, n in snap.items():
        m = _LEG_DISPATCHES.fullmatch(key)
        if m:
            steps += n * (int(m.group(2)) - int(m.group(1)))
    return snap["slot_steps_active"] / (max_slots * steps) if steps else 0.0


def run_multimodal(requests: int = 96, max_slots: int = 8,
                   step_ms: float = 2.0, max_wait_ms: float = 12.0,
                   inter_ms: float = 6.0, max_leg: int = 4, log=print,
                   registry_out=None):
    """ISSUE 10 tentpole gate: the three proxy workloads' native request
    shapes through ONE ContinuousGateway, exact-shape grouping vs the
    two-rung tier ladder, identical arrival schedule. The row reuses the
    generic field names — the baseline ("flush") arm here is exact-shape
    grouping, the "cont" arm is the tiered pool — so the CSV line,
    registry-p95 claims, and regression metrics apply unchanged."""
    events = schedule_multimodal(requests, inter_ms, burst=max_slots)

    def make(tiers):
        return lambda sampler, clock: ContinuousGateway(
            sampler, max_slots=max_slots, max_wait_ms=max_wait_ms,
            clock=clock, max_leg=max_leg, tiers=tiers)

    exact_waits, exact_stats, exact_snap, exact_resps = simulate(
        make(None), events, step_ms)
    tier_waits, tier_stats, tier_snap, tier_resps = simulate(
        make(ShapeLadder(TIER_RUNGS)), events, step_ms)

    # bit-identity: every sample from BOTH arms must equal the direct
    # sampler at the request's NATIVE shape (tier padding cropped away)
    oracle = ToyCarrySampler()
    mismatches = 0
    for (_, budget, i, rows_n), er, tr in zip(events, exact_resps,
                                              tier_resps):
        x0 = jax.random.normal(jax.random.PRNGKey(1000 + i), (rows_n, 2))
        want = np.asarray(oracle.sample_from(None, x0[None],
                                             oracle.resolve_budget(budget))[0])
        for got in (np.asarray(er.latents), np.asarray(tr.latents)):
            if got.shape != want.shape or not np.array_equal(got, want):
                mismatches += 1

    hist = tier_snap["wait_ms"]
    lo, hi = bucket_bounds_at(hist["bounds"], hist["buckets"], 95.0)
    width = float(hi - lo) if np.isfinite(hi) else float("inf")
    row = {
        "mix": "multimodal",
        "requests": requests,
        "max_slots": max_slots,
        "step_ms": step_ms,
        "tier_rungs": list(TIER_RUNGS),
        "exact_shape_groups": len({ev[3] for ev in events}),
        # generic names: flush_* = exact-shape arm, cont_* = tiered arm
        "flush_p95_wait_ms": float(np.percentile(exact_waits, 95)),
        "cont_p95_wait_ms": float(np.percentile(tier_waits, 95)),
        "flush_mean_wait_ms": float(exact_waits.mean()),
        "cont_mean_wait_ms": float(tier_waits.mean()),
        "p95_ratio": float(np.percentile(exact_waits, 95)
                           / max(np.percentile(tier_waits, 95), 1e-9)),
        "flush_forwards": exact_stats["forwards"],
        "cont_forwards": tier_stats["forwards"],
        "forwards_ratio": tier_stats["forwards"]
        / max(exact_stats["forwards"], 1),
        "flush_nfe_per_request": exact_stats["nfe_per_request"],
        "cont_nfe_per_request": tier_stats["nfe_per_request"],
        "joins": tier_stats["joins"],
        "join_rate": tier_stats["join_rate"],
        "trajectories": tier_stats["trajectories"],
        "exact_trajectories": exact_stats["trajectories"],
        # slot occupancy here is the trajectories' fill (``slot_fill``);
        # the gateways' dispatched-width ratio is reported beside it
        "slot_occupancy": slot_fill(tier_snap, max_slots),
        "exact_slot_occupancy": slot_fill(exact_snap, max_slots),
        "occupancy_gain": slot_fill(tier_snap, max_slots)
        / max(slot_fill(exact_snap, max_slots), 1e-9),
        "dispatched_occupancy": tier_stats["slot_occupancy"],
        "exact_dispatched_occupancy": exact_stats["slot_occupancy"],
        "mismatches": mismatches,
        "tier_occupancy_gauges": {
            k: v for k, v in tier_snap.items()
            if k.startswith("tier_occupancy{")},
        "cont_p95_wait_ms_registry": float(hist["p95"]),
        "registry_p95_bucket_width": width,
        "registry_p95_delta": float(
            abs(hist["p95"] - np.percentile(tier_waits, 95))),
        "wait_hist_count": int(hist["count"]),
    }
    if registry_out is not None:
        registry_out["multimodal"] = {"exact": exact_snap,
                                      "tiered": tier_snap}
    log(f"multimodal: slot_occupancy {row['exact_slot_occupancy']:.2f} "
        f"(exact-shape, {row['exact_shape_groups']} groups) -> "
        f"{row['slot_occupancy']:.2f} (tiered, {len(TIER_RUNGS)} rungs, "
        f"{row['occupancy_gain']:.2f}x); forwards {row['flush_forwards']} "
        f"-> {row['cont_forwards']}; trajectories "
        f"{row['exact_trajectories']} -> {row['trajectories']}; p95 wait "
        f"{row['flush_p95_wait_ms']:.1f}ms -> "
        f"{row['cont_p95_wait_ms']:.1f}ms; {row['mismatches']} bit-exact "
        f"mismatches")
    return row


def check_claims(rows):
    notes = []
    for r in rows:
        if r["mix"] == "mixed":
            ok = r["p95_ratio"] >= 1.5
            notes.append(f"[{'PASS' if ok else 'FAIL'}] continuous p95 wait "
                         f">= 1.5x better than flush-only at mixed budgets "
                         f"(got {r['p95_ratio']:.2f}x)")
            ok = r["forwards_ratio"] <= 1.05
            notes.append(f"[{'PASS' if ok else 'FAIL'}] continuous spends "
                         f"no more backbone forwards than flush-only at "
                         f"mixed budgets (ratio {r['forwards_ratio']:.3f})")
        elif r["mix"] == "skew16":
            # flush-only is near-optimal when one budget dominates (full
            # single-budget buckets); continuous must not burn forwards
            ok = r["forwards_ratio"] <= 1.10
            notes.append(f"[{'PASS' if ok else 'FAIL'}] continuous stays "
                         f"within 10% of flush-only forwards on the "
                         f"skew16 workload (ratio {r['forwards_ratio']:.3f})")
        elif r["mix"] == "multimodal":
            ok = r["slot_occupancy"] > r["exact_slot_occupancy"]
            notes.append(f"[{'PASS' if ok else 'FAIL'}] multimodal: tiered "
                         f"pool reaches strictly higher slot occupancy "
                         f"than exact-shape grouping "
                         f"({r['slot_occupancy']:.3f} vs "
                         f"{r['exact_slot_occupancy']:.3f})")
            ok = r["cont_forwards"] <= r["flush_forwards"]
            notes.append(f"[{'PASS' if ok else 'FAIL'}] multimodal: tiered "
                         f"pool spends no more total forwards than "
                         f"exact-shape grouping ({r['cont_forwards']} vs "
                         f"{r['flush_forwards']})")
            ok = r["mismatches"] == 0
            notes.append(f"[{'PASS' if ok else 'FAIL'}] multimodal: every "
                         f"sample bit-identical to the direct sampler at "
                         f"its native shape, both arms "
                         f"({r['mismatches']} mismatches)")
        ok = (r["registry_p95_delta"]
              <= r["registry_p95_bucket_width"] + 1e-9)
        notes.append(f"[{'PASS' if ok else 'FAIL'}] {r['mix']}: registry "
                     f"histogram p95 within one bucket width of "
                     f"np.percentile (delta {r['registry_p95_delta']:.2f}ms"
                     f" <= width {r['registry_p95_bucket_width']:.2f}ms)")
        ok = r["wait_hist_count"] == r["requests"]
        notes.append(f"[{'PASS' if ok else 'FAIL'}] {r['mix']}: wait "
                     f"histogram count == settled requests "
                     f"({r['wait_hist_count']} vs {r['requests']})")
    return notes


def metrics(rows):
    """Regression-gate metrics (benchmarks/regression.py schema). The
    simulation is deterministic, so the default 15% tolerance is slack."""
    out = {}
    for r in rows:
        out[f"{r['mix']}.p95_ratio"] = {
            "value": round(r["p95_ratio"], 4), "higher_better": True}
        out[f"{r['mix']}.forwards_ratio"] = {
            "value": round(r["forwards_ratio"], 4), "higher_better": False}
        out[f"{r['mix']}.join_rate"] = {
            "value": round(r["join_rate"], 4), "higher_better": True}
        # deterministic registry metrics: the histogram count is exact and
        # the interpolated p95 rides the same fake clock as the waits
        out[f"{r['mix']}.wait_hist_count"] = {
            "value": r["wait_hist_count"], "higher_better": True}
        out[f"{r['mix']}.cont_p95_wait_ms_registry"] = {
            "value": round(r["cont_p95_wait_ms_registry"], 4),
            "higher_better": False}
        if r["mix"] == "multimodal":
            out["multimodal.occupancy_gain"] = {
                "value": round(r["occupancy_gain"], 4),
                "higher_better": True}
            out["multimodal.slot_occupancy"] = {
                "value": round(r["slot_occupancy"], 4),
                "higher_better": True}
            out["multimodal.mismatches"] = {
                "value": r["mismatches"], "higher_better": False}
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=96)
    ap.add_argument("--max-slots", type=int, default=8)
    ap.add_argument("--step-ms", type=float, default=2.0)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--json", default=None,
                    help="write the summary (rows + claims + metrics) here")
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero when an acceptance claim FAILs")
    args = ap.parse_args()
    requests = 48 if args.quick else args.requests
    rows = run(requests=requests, max_slots=args.max_slots,
               step_ms=args.step_ms)
    notes = check_claims(rows)
    for n in notes:
        print(n)
    for r in rows:
        print(f"continuous/{r['mix']},{r['cont_p95_wait_ms'] * 1e3:.1f},"
              f"p95_ratio={r['p95_ratio']:.2f};"
              f"forwards_ratio={r['forwards_ratio']:.3f};"
              f"join_rate={r['join_rate']:.2f}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"bench": "continuous", "rows": rows, "claims": notes,
                       "metrics": metrics(rows)}, f, indent=2)
        print(f"summary written to {args.json}")
    if args.check and any(n.startswith("[FAIL]") for n in notes):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
