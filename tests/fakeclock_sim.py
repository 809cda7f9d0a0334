"""Fake-clock replay driver for the serving tiers' scheduling tests.

``replay`` feeds a seeded arrival schedule through one gateway whose clock
is a ``FakeClock``. Time moves only by backbone forwards x ``step_ms``
(ticked from the sampler or engine, so an arrival due mid-dispatch lands
before the next plan) and, when the gateway has nothing to run, by a hop
to the next arrival or device horizon. Waits, forward counts and the
registry snapshot are therefore exact functions of the schedule and the
gateway's policy: the tests compare two policies on one schedule.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Callable, Optional

import jax
import numpy as np

from repro.serving import AdmissionRejected, FleetGateway, Gateway, Request
from repro.serving.toy import CountingToySampler, FakeClock

# budget mixes, indexed by request number
MIXED = lambda i: (4, 8, 16)[i % 3]
SKEW16 = lambda i: 16 if i % 4 else 4


class SimSampler(CountingToySampler):
    """The eager counting toy sampler with its velocity field jitted (the
    forward hook still fires once per batch-level forward) and ``tick``
    called after each forward."""

    def __init__(self, budgets=(4, 8, 16), tick=None):
        super().__init__(budgets=budgets)
        self.tick = tick
        self._field = jax.jit(self.field.fn)

    def _u(self, t, x):
        self.on_forward()
        return self._field(t, x)

    def on_forward(self):
        super().on_forward()
        if self.tick is not None:
            self.tick()


def stream(budget_of, requests: int, inter_ms: float, burst: int,
           rows_of: Optional[Callable[[int], int]] = None, **kw):
    """An opening burst at t=0, then one arrival every ``inter_ms``:
    ``[(arrive_s, Request)]``. ``rows_of(i)`` gives request i an x0 of
    shape (rows, 2); ``kw`` goes to every ``Request``."""
    times = [0.0 if i < burst else (i - burst + 1) * inter_ms / 1e3
             for i in range(requests)]
    return arrivals(times, budget_of, rows_of, **kw)


def poisson(budget_of, requests: int, mean_gap_s: float, seed: int = 0,
            **kw):
    """Open-loop Poisson arrivals (first at t=0), seeded."""
    gaps = np.random.default_rng(seed).exponential(mean_gap_s, requests)
    times = (np.cumsum(gaps) - gaps[0]).tolist()
    return arrivals(times, budget_of, **kw)


def arrivals(times, budget_of, rows_of=None, seed: int = 1000, **kw):
    """``[(arrive_s, Request)]`` at ``times``, x0 drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    out = []
    for i, t in enumerate(times):
        shape = (rows_of(i), 2) if rows_of else (2,)
        x0 = rng.standard_normal(shape).astype(np.float32)
        out.append((t, Request(budget=budget_of(i), x0=x0, **kw)))
    return out


@dataclasses.dataclass
class Replay:
    waits: np.ndarray            # wait_ms of every settled request
    stats: dict
    snapshot: dict               # the gateway's registry snapshot


def replay(make_gateway, events, step_ms: float = 2.0) -> Replay:
    """Drive ``make_gateway(clock, tick)`` through ``events``
    (``[(arrive_s, request)]``, sorted) to its last settled future.
    ``tick`` advances the clock by one forward and submits what has come
    due; pass it to the sampler or engine. A gateway may carry
    ``wake() -> [times]``: device horizons the idle clock may hop to.
    Rejected submits are dropped; shed futures count as settled."""
    clock = FakeClock()
    pending = deque(events)
    futures = []

    def submit_due():
        while pending and pending[0][0] <= clock.t + 1e-12:
            try:
                futures.append(gw.submit(pending.popleft()[1]))
            except AdmissionRejected:
                pass

    def tick():
        clock.advance(step_ms / 1e3)
        submit_due()

    gw = make_gateway(clock, tick)
    wake = getattr(gw, "wake", list)
    while True:
        submit_due()
        if gw.pump():
            continue
        if not pending and all(f.done() for f in futures):
            break
        hops = wake()
        if pending:
            hops.append(pending[0][0])
        clock.advance((min(hops) if hops else clock.t + step_ms / 2e3)
                      - clock.t)
    waits = [f.result().meta["wait_ms"] for f in futures
             if f.exception() is None]
    return Replay(np.array(waits), gw.stats(), gw.metrics_snapshot())


class ParallelFleet:
    """A ``FleetGateway`` of four hosts that run in parallel: a host
    dispatches only while free and is then busy for its forwards x
    ``STEP_MS``.
    Stealing moves queue entries only, so it costs no time; the thieves
    are the hosts that are free and empty."""

    STEP_MS = 2.0

    def __init__(self, clock, stealer=None, recorder=None):
        self.clock = clock
        self.samplers = {f"h{i}": SimSampler() for i in range(4)}
        self.hosts = {n: Gateway(s, max_batch=8, max_wait_ms=12.0,
                                 mixed_budget_policy="never", clock=clock)
                      for n, s in self.samplers.items()}
        # router seed 1 homes the three budget keys on three distinct
        # hosts: the uniform mix is balanced, skew16 has one hot shard
        self.fleet = FleetGateway(self.hosts, stealer=stealer,
                                  steal=stealer is not None, seed=1,
                                  recorder=recorder)
        self.busy = dict.fromkeys(self.hosts, 0.0)

    def submit(self, request):
        return self.fleet.submit(request)

    def _free(self, name):
        return self.busy[name] <= self.clock.t + 1e-12

    def pump(self) -> int:
        ran = 0
        for name, gw in sorted(self.hosts.items()):
            if self._free(name):
                before = self.samplers[name].forwards
                if gw.pump():
                    self.busy[name] = self.clock.t + (
                        self.samplers[name].forwards - before
                    ) * self.STEP_MS / 1e3
                    ran += 1
        thieves = [n for n, gw in self.hosts.items()
                   if self._free(n) and gw.queue.depth() == 0]
        return self.fleet.steal_round(thieves=thieves) + ran

    def wake(self):
        return [t for t in self.busy.values() if t > self.clock.t]

    def stats(self):
        return self.fleet.stats()

    def metrics_snapshot(self):
        return self.fleet.metrics_snapshot()
