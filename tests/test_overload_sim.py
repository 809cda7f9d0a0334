"""SLO scheduling vs FIFO under sustained overload on the fake clock.

Open-loop Poisson arrivals at 100x the gateway's derived capacity replay
through two arms of the same gateway, on the flush and the continuous
tier: FIFO (``slo=None``: deadlines recorded, nothing rejected, shed or
reordered) and SLO (fast-reject admission seeded with one derived
dispatch cost, shedding, urgency order, and on the continuous tier
exit-boundary preemption). FIFO's backlog outlives the deadline, so only
its first batches settle in time while the device serves a hopeless
tail; admission keeps the queue no deeper than the deadline absorbs.
The worst dispatch (budget 8 x 1 ms) sits well under the 20 ms deadline,
and the arrival window (requests x gap) spans about three deadlines, so
policy and not service granularity decides who is late.
"""
import dataclasses
import functools

import pytest
from fakeclock_sim import SimSampler, arrivals, poisson, replay

from repro.serving import ContinuousGateway, Gateway, SLOConfig

BUDGETS = (2, 4, 8)
SLOTS = 8
STEP_MS = 1.0
MAX_WAIT_MS = 12.0
DEADLINE_MS = 20.0
OVERLOAD = 100.0                # offered load / derived capacity
REQUESTS = 10800
# steady state: full single-budget buckets amortize a budget-b dispatch
# (b forwards x STEP_MS) over SLOTS rows, the budget mix cycling the grid
DISPATCH_MS = sum(BUDGETS) / len(BUDGETS) * STEP_MS
CAPACITY_MS = DISPATCH_MS / SLOTS           # per request


def _flush(slo):
    return lambda clock, tick: Gateway(
        SimSampler(BUDGETS, tick), max_batch=SLOTS, max_wait_ms=MAX_WAIT_MS,
        clock=clock, slo=slo)


def _continuous(slo):
    return lambda clock, tick: ContinuousGateway(
        SimSampler(BUDGETS, tick), max_slots=SLOTS, max_wait_ms=MAX_WAIT_MS,
        clock=clock, max_leg=4, slo=slo)


TIERS = {
    # admission seeded with one derived dispatch (mean budget x step);
    # the slack absorbs one worst bucket
    "flush": (_flush, SLOConfig(slack_ms=8.0,
                                default_cost_ms=DISPATCH_MS)),
    # slots refill at every exit boundary: the seed is the first
    # boundary's leg (2 forwards x step); a quarter of requests urgent
    "continuous": (_continuous, SLOConfig(slack_ms=6.0,
                                          default_cost_ms=2.0)),
}


@functools.cache
def _events():
    return poisson(lambda i: BUDGETS[i % 3], REQUESTS,
                   CAPACITY_MS / OVERLOAD / 1e3, deadline_ms=DEADLINE_MS)


@functools.cache
def _arms(tier):
    make, slo = TIERS[tier]
    events = _events()
    if tier == "continuous":
        events = [(t, dataclasses.replace(r, priority=int(i % 4 == 0)))
                  for i, (t, r) in enumerate(events)]
    fifo = replay(make(None), events, STEP_MS).stats
    return fifo, replay(make(slo), events, STEP_MS).stats


def test_offered_load_is_100x_capacity():
    window_ms = _events()[-1][0] * 1e3
    assert REQUESTS / window_ms * CAPACITY_MS >= 100.0


def _accounted(s):
    return s["goodput"] + s["deadline_misses"] + s["rejected"]


OVERLOAD_CLAIMS = {
    "slo_goodput_beats_fifo": lambda f, s: s["goodput"] > f["goodput"],
    "slo_hit_rate_beats_fifo":
        lambda f, s: s["deadline_hit_rate"] > f["deadline_hit_rate"],
    "slo_spends_no_more_forwards":
        lambda f, s: s["forwards"] <= f["forwards"],
    "every_deadline_request_accounted":
        lambda f, s: _accounted(f) == _accounted(s) == REQUESTS,
    # admitted work lands at most one worst dispatch past the deadline;
    # FIFO's tail is its whole backlog
    "slo_p99_wait_within_deadline_plus_one_dispatch":
        lambda f, s: (s["wait_p99_ms"] <= DEADLINE_MS + max(BUDGETS) * STEP_MS
                      and s["wait_p99_ms"] < f["wait_p99_ms"] / 10),
    "admission_cost_model_calibrates":
        lambda f, s: s["cost_est_samples"] > 0,
}


@pytest.mark.parametrize("claim", OVERLOAD_CLAIMS)
@pytest.mark.parametrize("tier", TIERS)
def test_slo_beats_fifo_at_100x_overload(tier, claim):
    assert OVERLOAD_CLAIMS[claim](*_arms(tier))


@functools.cache
def _preempt():
    """8 best-effort budget-8 requests seize every slot at t=0; 4 urgent
    budget-4 requests with a 10 ms deadline land mid-leg. No slot frees
    before budget 8, past their deadline, unless the SLO arm preempts at
    the first exit boundary and resumes the victims from their carry."""
    events = (arrivals([0.0] * 8, lambda i: 8)
              + arrivals([1.5e-3] * 4, lambda i: 4, seed=1008,
                         deadline_ms=10.0, priority=1))
    make, slo = TIERS["continuous"]
    return (replay(make(None), events, STEP_MS).stats,
            replay(make(slo), events, STEP_MS).stats)


PREEMPT_CLAIMS = {
    "slo_preempts_fifo_never":
        lambda f, s: s["preemptions"] > 0 and f["preemptions"] == 0,
    "preemption_serves_the_deadlines_fifo_misses":
        lambda f, s: s["goodput"] == 4 and f["goodput"] < 4,
    "victims_resume_and_every_deadline_is_accounted":
        lambda f, s: (f["completed"] == s["completed"] == 12
                      and _accounted(f) == _accounted(s) == 4),
}


@pytest.mark.parametrize("claim", PREEMPT_CLAIMS)
def test_preemption_serves_urgent_tier(claim):
    assert PREEMPT_CLAIMS[claim](*_preempt())
