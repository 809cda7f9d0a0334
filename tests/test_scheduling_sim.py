"""Scheduling properties of the serving tiers on the fake clock: continuous
vs flush-only batching, shape tiers vs exact-shape grouping, work stealing
vs static routing, and decode refill, chunked prefill and paged KV. Each
case compares two policies over one seeded schedule (``fakeclock_sim``);
the waits are simulated milliseconds, never wall time."""
import functools
import re

import numpy as np
import pytest
from fakeclock_sim import (MIXED, SKEW16, ParallelFleet, SimSampler, replay,
                           stream)

from repro.observability import bucket_bounds_at
from repro.serving import ContinuousGateway, Gateway, ShapeLadder, WorkStealer
from repro.serving.decode import DecodeGateway, DecodeRequest
from repro.serving.toy import ToyDecodeEngine

SLOTS = 8
MAX_WAIT_MS = 12.0
MIXES = {"mixed": MIXED, "skew16": SKEW16}
# native request rows of the three proxy workloads: taxonomy text, audio
# infill, image latents; round-robin by request, each cycling its lengths
MODALITIES = ((5, 7, 8), (10, 13, 16), (16,))
# a shard is a victim only once it holds a full batch it cannot flush:
# shallower queues are cheaper to serve at home
STEALER = WorkStealer(min_queue=SLOTS, max_steal=SLOTS // 2)


def _rows(i):
    lengths = MODALITIES[i % 3]
    return lengths[(i // 3) % len(lengths)]


def _flush(clock, tick):
    return Gateway(SimSampler(tick=tick), max_batch=SLOTS,
                   max_wait_ms=MAX_WAIT_MS, clock=clock)


def _continuous(tiers=None):
    return lambda clock, tick: ContinuousGateway(
        SimSampler(tick=tick), max_slots=SLOTS, max_wait_ms=MAX_WAIT_MS,
        clock=clock, max_leg=4, tiers=tiers)


@functools.cache
def _runs(case):
    """(baseline, policy) replays of one scenario, 48 requests each.
    Moderate steady load: service keeps up and buckets do not fill before
    ``max_wait_ms``, the regime continuous batching targets."""
    if case in MIXES:
        events = stream(MIXES[case], 48, inter_ms=6.0, burst=SLOTS)
        return replay(_flush, events), replay(_continuous(), events)
    if case == "multimodal":    # six native shapes: exact grouping vs tiers
        events = stream(MIXED, 48, inter_ms=6.0, burst=SLOTS, rows_of=_rows)
        return (replay(_continuous(), events),
                replay(_continuous(ShapeLadder((8, 16))), events))
    mix = {"fleet_skew16": SKEW16, "fleet_uniform": MIXED}[case]
    events = stream(mix, 48, inter_ms=2.0, burst=SLOTS)
    return (replay(lambda clock, tick: ParallelFleet(clock), events),
            replay(lambda clock, tick: ParallelFleet(clock, STEALER), events))


_LEG = re.compile(r'dispatches\{program="leg/(\d+)-(\d+)-k\d+"\}')


def _slot_fill(snap):
    """Live slot-steps over all ``SLOTS`` slots of every leg step: how
    full the trajectories run. The gateway's ``slot_occupancy`` divides
    by the slot-steps dispatched, and a leg dispatches only its live
    slots' power of two, so that ratio does not see the packing."""
    steps = sum(n * (int(m.group(2)) - int(m.group(1)))
                for key, n in snap.items() if (m := _LEG.fullmatch(key)))
    return snap["slot_steps_active"] / (SLOTS * steps)


def _forwards_ratio(base, new):
    return new.stats["forwards"] / base.stats["forwards"]


def _p95_ratio(base, new):
    return np.percentile(base.waits, 95) / np.percentile(new.waits, 95)


CLAIMS = {
    # continuous admits at exit boundaries what flush-only ages out
    "mixed_p95_wait_1.5x_below_flush":
        ("mixed", lambda b, n: _p95_ratio(b, n) >= 1.5),
    "mixed_forwards_within_5pct_of_flush":
        ("mixed", lambda b, n: _forwards_ratio(b, n) <= 1.05),
    # flush-only is near-optimal when one budget dominates
    "skew16_forwards_within_10pct_of_flush":
        ("skew16", lambda b, n: _forwards_ratio(b, n) <= 1.10),
    "multimodal_tiers_fill_slots_strictly_better":
        ("multimodal",
         lambda b, n: _slot_fill(n.snapshot) > _slot_fill(b.snapshot)),
    "multimodal_tiers_spend_no_more_forwards":
        ("multimodal",
         lambda b, n: n.stats["forwards"] <= b.stats["forwards"]),
    # one hot affinity key saturates its home host; the rest idle
    "fleet_skew16_stealing_beats_static_p95":
        ("fleet_skew16", lambda b, n: _p95_ratio(b, n) > 1.0),
    "fleet_skew16_stealing_steals":
        ("fleet_skew16", lambda b, n: n.stats["steals"] > 0),
    # affinity already balances the fleet: stealing must not hurt
    "fleet_uniform_stealing_keeps_p95":
        ("fleet_uniform", lambda b, n: _p95_ratio(b, n) >= 0.9),
    "fleet_uniform_stealing_forwards_within_25pct":
        ("fleet_uniform", lambda b, n: _forwards_ratio(b, n) <= 1.25),
}


@pytest.mark.parametrize("claim", CLAIMS)
def test_policy_beats_baseline(claim):
    case, holds = CLAIMS[claim]
    assert holds(*_runs(case))


@pytest.mark.parametrize("case", [*MIXES, "multimodal", "fleet_skew16",
                                  "fleet_uniform"])
def test_wait_histogram_counts_every_settled_request(case):
    for run in _runs(case):
        assert run.snapshot["wait_ms"]["count"] == len(run.waits) == 48


@pytest.mark.parametrize("case", [*MIXES, "multimodal"])
def test_registry_p95_within_one_bucket_of_exact(case):
    run = _runs(case)[1]
    hist = run.snapshot["wait_ms"]
    lo, hi = bucket_bounds_at(hist["bounds"], hist["buckets"], 95.0)
    width = hi - lo if np.isfinite(hi) else np.inf
    assert abs(hist["p95"] - np.percentile(run.waits, 95)) <= width + 1e-9


# -- decode: refill, chunked prefill, paged KV -------------------------------

CACHE_SLOTS = 64        # the dense per-slot KV the paged run must beat
PROMPT_LENS = (2, 24, 6, 1, 12, 18)
OUTPUT_LENS = {"mixed": (32, 4, 16, 8), "uniform": (16, 16, 16, 16)}


@functools.cache
def _decode(mix, refill=True, prefill_chunk=64, page_size=0):
    """32 requests queued at t=0; every engine invocation is one forward."""
    events = []
    for i in range(32):
        prompt = [(7 * i + 3 + j) % 97
                  for j in range(PROMPT_LENS[i % len(PROMPT_LENS)])]
        events.append((0.0, DecodeRequest(
            prompt=prompt, max_tokens=OUTPUT_LENS[mix][i % 4])))
    return replay(lambda clock, tick: DecodeGateway(
        ToyDecodeEngine(on_step=tick, page_size=page_size),
        max_slots=SLOTS, cache_slots=CACHE_SLOTS, refill=refill,
        prefill_chunk=prefill_chunk, clock=clock), events).stats


def test_decode_chunked_prefill_strictly_fewer_steps_than_teacher_forced():
    assert (_decode("mixed")["forwards"]
            < _decode("mixed", prefill_chunk=0)["forwards"])


def test_decode_paged_peak_kv_per_slot_below_dense():
    assert _decode("mixed", page_size=8)["peak_kv_per_slot"] < CACHE_SLOTS


def test_decode_mixed_lengths_admit_mid_flight():
    assert _decode("mixed")["joins"] > 0


def test_decode_refill_never_worse_at_uniform_lengths():
    assert (_decode("uniform")["forwards"]
            <= _decode("uniform", refill=False)["forwards"])

