"""Continuous batching: trajectory slot admission/release at exit
boundaries (fake clock), mid-flight joins with prefix forwards accounting,
bit-identity of every continuously-batched sample vs the direct sampler,
legs narrowed to their live slots, interleaved flushes for non-joinable
requests, drain, and the carry protocol on the real smoke backbone."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.anytime import init_anytime
from repro.serving import AnytimeFlowSampler, ContinuousGateway, Request
from repro.serving.continuous import ContinuousScheduler
from repro.serving.gateway import _Entry
from repro.serving.slo import SLOConfig
from repro.serving.toy import CountingToySampler, FakeClock, ToyAnytimeSampler
from repro.solvers import SolverArtifact, SolverSpec

BUDGETS = (2, 4, 8)


class CountingCarrySampler(CountingToySampler):
    """The shared counting toy sampler at this suite's (2, 4, 8) budgets —
    the carry protocol (and its forward accounting) comes with it."""

    def __init__(self, budgets=BUDGETS, seed=0, jitter=0.1):
        super().__init__(budgets=budgets, seed=seed, jitter=jitter)


def _gateway(sampler=None, **kw):
    clock = FakeClock()
    sampler = sampler or CountingCarrySampler()
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_wait_ms", 10.0)
    gw = ContinuousGateway(sampler, clock=clock, **kw)
    return gw, sampler, clock


def _x0(i, shape=(2,)):
    return jax.random.normal(jax.random.PRNGKey(100 + i), shape)


def _direct(x0s, budget):
    """Reference samples from a FRESH sampler (same theta, same arithmetic)."""
    return CountingCarrySampler().sample_from(None, jnp.stack(x0s), budget)


def _leg_widths(gw) -> dict:
    """``{"<a>-<b>": [widths]}`` of the legs dispatched, from the
    ``dispatches{program="leg/<a>-<b>-k<w>"}`` counters."""
    out: dict = {}
    for key, v in gw.metrics.snapshot().items():
        m = re.fullmatch(r'dispatches\{program="leg/(\d+-\d+)-k(\d+)"\}', key)
        if m:
            out.setdefault(m.group(1), []).extend([int(m.group(2))] * v)
    return {leg: sorted(ws) for leg, ws in out.items()}


def _entry(uid, served, t=0.0):
    return _Entry(uid=uid, tokens=None, x0=jnp.zeros((2,)), requested=served,
                  served=served, shape_key=(None, (2,)), t_submit=t,
                  future=None)


# ---------------------------------------------------------------------------
# ContinuousScheduler (pure planning)
# ---------------------------------------------------------------------------


def test_plan_start_waits_until_full_or_aged():
    s = ContinuousScheduler(max_slots=2, boundaries=BUDGETS, max_wait_ms=10.0)
    young = [_entry(0, 4)]
    assert s.plan_start(young, now=0.005) == []
    assert [e.uid for e in s.plan_start(young, now=0.011)] == [0]   # aged
    assert [e.uid for e in s.plan_start(young, now=0.0, force=True)] == [0]
    full = [_entry(i, 4) for i in range(3)]
    assert [e.uid for e in s.plan_start(full, now=0.0)] == [0, 1]  # capped


def test_plan_joins_filters_budget_shape_and_slots():
    s = ContinuousScheduler(max_slots=4, boundaries=BUDGETS)
    pending = [_entry(0, 2), _entry(1, 8), _entry(2, 4), _entry(3, 8)]
    # budget must lie strictly beyond the boundary
    got = s.plan_joins(pending, boundary=4, free_slots=4,
                       shape_key=(None, (2,)))
    assert [e.uid for e in got] == [1, 3]
    # FIFO capped by free slots
    got = s.plan_joins(pending, boundary=2, free_slots=2,
                       shape_key=(None, (2,)))
    assert [e.uid for e in got] == [1, 2]
    # other sample shapes never share a trajectory
    assert s.plan_joins(pending, 2, 4, shape_key=(None, (3,))) == []
    assert s.plan_joins(pending, 2, 0, shape_key=(None, (2,))) == []


def test_join_bucket_and_next_boundary():
    s = ContinuousScheduler(max_slots=8, boundaries=BUDGETS)
    assert [s.join_bucket(k) for k in (1, 2, 3, 8)] == [1, 2, 4, 8]
    with pytest.raises(ValueError):
        s.join_bucket(9)
    assert s.next_boundary(0) == 2
    assert s.next_boundary(2) == 4
    assert s.next_boundary(7) == 8
    assert s.next_boundary(8) is None
    with pytest.raises(ValueError):
        ContinuousScheduler(max_slots=0, boundaries=BUDGETS)


# ---------------------------------------------------------------------------
# Trajectory lifecycle (fake clock, manual pump)
# ---------------------------------------------------------------------------


def test_trajectory_releases_each_budget_at_its_boundary():
    gw, sampler, clock = _gateway()
    futs = {b: gw.submit(Request(budget=b, x0=_x0(b))) for b in (2, 4)}
    clock.advance(1.0)
    assert gw.pump() == 1                       # trajectory opens (0 forwards)
    assert sampler.forwards == 0
    assert gw.pump() == 1                       # leg 0..2: budget-2 exits
    assert futs[2].done() and not futs[4].done()
    assert sampler.forwards == 2
    assert gw.pump() == 1                       # leg 2..4: budget-4 exits
    assert futs[4].done()
    assert sampler.forwards == 4                # max(budgets present), not sum
    assert gw._traj is None                     # all slots released
    s = gw.stats()
    assert s["trajectories"] == 1 and s["legs"] == 2 and s["joins"] == 0


@pytest.mark.parametrize("slots,starters,joiners,widths", [
    # releases narrow the legs 4 -> 2 -> 1
    (4, (2, 4, 8), (), {"0-2": [4], "2-4": [2], "4-8": [1]}),
    # a join at 2 widens to the full carry, a release narrows again
    (4, (4, 8), (8,), {"0-2": [2], "2-4": [4], "4-8": [2]}),
    # one live row of 8 slots at width 1, two joiners, then a release
    (8, (8,), (8, 4), {"0-2": [1], "2-4": [4], "4-8": [2]}),
])
def test_continuous_samples_bit_identical_to_direct_sampler(
        slots, starters, joiners, widths):
    """Every sample equals the direct sampler's, bit for bit, while the
    legs run at the width of their live slots and joins and releases
    change that width mid-trajectory."""
    gw, sampler, clock = _gateway(max_slots=slots)
    budgets = starters + joiners
    x0s = [_x0(i) for i in range(len(budgets))]
    futs = [gw.submit(Request(budget=b, x0=x))
            for b, x in zip(starters, x0s)]
    clock.advance(1.0)
    assert gw.pump() == 1                       # trajectory opens
    futs += [gw.submit(Request(budget=b, x0=x))
             for b, x in zip(joiners, x0s[len(starters):])]
    gw.drain()                                  # joiners enter at 2
    for i, (fut, b, x0) in enumerate(zip(futs, budgets, x0s)):
        direct = _direct([x0], b)[0]
        np.testing.assert_array_equal(np.asarray(fut.result().latents),
                                      np.asarray(direct))
        meta = fut.result().meta
        assert meta["continuous"] and meta["served_budget"] == b
        assert meta["join_step"] == (0 if i < len(starters) else 2)
    assert _leg_widths(gw) == widths


def test_leg_dispatches_the_power_of_two_of_its_live_slots():
    """With 8 slots and 3 live rows the leg runs 4 rows: the dispatch
    label, the slot-steps paid for and the response's padded size say
    so, while the forwards and the real rows stay those of the leg."""
    gw, sampler, clock = _gateway(max_slots=8)
    futs = [gw.submit(Request(budget=2, x0=_x0(i))) for i in range(3)]
    clock.advance(1.0)
    gw.drain()
    snap = gw.metrics.snapshot()
    assert snap['dispatches{program="leg/0-2-k4"}'] == 1
    assert _leg_widths(gw) == {"0-2": [4]}
    assert snap["slot_steps_total"] == 4 * 2
    assert snap["slot_steps_active"] == 3 * 2
    assert snap['forwards_by_rows{rows="3"}'] == 2
    assert sampler.forwards == 2
    for f in futs:
        meta = f.result().meta
        assert meta["batch_real"] == 3 and meta["batch_padded"] == 4


def test_preempted_and_streaming_slots_keep_their_bits_across_widths():
    """A slot preempted at a full-width boundary resumes into a narrowed
    leg, and a streaming slot's partials and final cross the same width
    change: every payload equals the direct sampler's, bit for bit."""
    clock = FakeClock()
    gw = ContinuousGateway(CountingCarrySampler(), max_slots=4,
                           max_wait_ms=10.0, clock=clock, slo=SLOConfig())
    stream = gw.submit_stream(budget=8, x0=_x0(0))
    low = gw.submit(Request(budget=8, x0=_x0(1)))
    short = [gw.submit(Request(budget=2, x0=_x0(2 + i))) for i in range(2)]
    assert gw.pump(force=True) == 1              # 4 slots: opens full
    hot = [gw.submit(Request(budget=4, x0=_x0(4 + i), priority=1))
           for i in range(3)]
    gw.pump()     # leg 0-2 at 4: shorts exit, two hots join, one preempts
    assert gw.stats()["preemptions"] == 1 and not low.done()
    gw.pump()     # leg 2-4 at 4: hots exit, the victim resumes at 4
    gw.pump()     # leg 4-8 at 2: the stream and the victim exit
    assert _leg_widths(gw) == {"0-2": [4], "2-4": [4], "4-8": [2]}
    for f, b, i in [(low, 8, 1), (short[0], 2, 2), (short[1], 2, 3),
                    (hot[0], 4, 4), (hot[1], 4, 5), (hot[2], 4, 6)]:
        np.testing.assert_array_equal(np.asarray(f.result(1).latents),
                                      np.asarray(_direct([_x0(i)], b)[0]))
    chunks = stream.chunks(timeout=1)
    assert [c.meta.get("boundary") for c in chunks[:-1]] == [2, 4]
    for c, b in zip(chunks, (2, 4, 8)):
        payload = c.payload.latents if c.final else c.payload
        np.testing.assert_array_equal(np.asarray(payload),
                                      np.asarray(_direct([_x0(0)], b)[0]))


def test_first_trajectory_warms_the_narrow_legs():
    """The first trajectory of a shape runs every narrow (leg, width)
    program once; trajectories of any live count afterwards compile
    nothing more."""
    gw, sampler, clock = _gateway(ToyAnytimeSampler(budgets=BUDGETS),
                                  max_slots=8)

    def trajectory(budgets):
        futs = [gw.submit(Request(budget=b, x0=_x0(i)))
                for i, b in enumerate(budgets)]
        clock.advance(1.0)
        while not all(f.done() for f in futs):
            gw.pump(force=True)
        return futs

    trajectory((8,) * 8)                         # full width, then warm
    warm = gw.metrics.snapshot()["compilations"]
    for live in range(1, 8):
        trajectory(((2, 4, 8) * 3)[:live])
    assert gw.metrics.snapshot()["compilations"] == warm
    assert {w for ws in _leg_widths(gw).values() for w in ws} == {1, 2, 4, 8}


def test_a_warm_that_raises_is_tried_again():
    """A shape counts as warm only once its warm-up has returned: a warm
    that raises fails the starters, and the next trajectory of the shape
    warms again and serves."""
    class FlakyWarm(CountingCarrySampler):
        warms = 0

        def carry_warm(self, batch, carry, stop):
            self.warms += 1
            if self.warms == 1:
                raise RuntimeError("warm boom")

    gw, sampler, clock = _gateway(FlakyWarm())
    first = gw.submit(Request(budget=8, x0=_x0(0)))
    assert gw.pump(force=True) == 1
    with pytest.raises(RuntimeError, match="warm boom"):
        first.result(timeout=0)
    second = gw.submit(Request(budget=8, x0=_x0(1)))
    gw.drain()
    np.testing.assert_array_equal(np.asarray(second.result(1).latents),
                                  np.asarray(_direct([_x0(1)], 8)[0]))
    assert sampler.warms > 1


@pytest.mark.parametrize("missing", ["carry_start", "carry_extend",
                                     "carry_warm"])
def test_sampler_without_the_carry_protocol_is_refused(missing):
    """A sampler lacking any carry method fails at construction, not by
    silently serving legs some other way."""
    methods = {m: (lambda self, *a: None)
               for m in ("carry_start", "carry_extend", "carry_warm")
               if m != missing}
    sampler = type("Partial", (), {"budgets": BUDGETS, **methods})()
    with pytest.raises(TypeError, match=missing):
        ContinuousGateway(sampler, max_slots=4)


def test_join_mid_flight_costs_at_most_budget_incremental_forwards():
    """Acceptance: a request joining an in-flight trajectory at boundary k
    adds exactly k prefix forwards (and at most b total incremental),
    and its sample is bit-identical to the direct sampler."""
    gw, sampler, clock = _gateway()
    starters = [gw.submit(Request(budget=8, x0=_x0(i))) for i in range(2)]
    clock.advance(1.0)
    assert gw.pump() == 1                       # trajectory opens
    assert gw.pump() == 1                       # leg 0..2
    x_late = _x0(9)
    late = gw.submit(Request(budget=8, x0=x_late))    # arrives mid-flight
    before = sampler.forwards
    assert gw.pump() == 1                       # leg 2..4, then the join
    meta_counts = sampler.forwards - before
    assert meta_counts == 2 + 4                 # leg (2) + prefix 0..4 (4)
    assert gw.pump() == 1                       # leg 4..8: everyone exits
    for f in starters + [late]:
        assert f.done()
    incremental = sampler.forwards - 8          # vs a starters-only flight
    assert incremental == 4                     # == join boundary, <= 8
    np.testing.assert_array_equal(np.asarray(late.result().latents),
                                  np.asarray(_direct([x_late], 8)[0]))
    meta = late.result().meta
    assert meta["join_step"] == 4 and meta["continuous"]
    s = gw.stats()
    assert s["joins"] == 1 and s["join_rate"] == pytest.approx(1 / 3)


def test_released_slot_is_rejoined_and_trajectory_extends():
    """A slot freed at boundary k is reusable immediately; a joiner whose
    budget exceeds every active budget extends the trajectory's life."""
    gw, sampler, clock = _gateway(max_slots=2)
    f2 = gw.submit(Request(budget=2, x0=_x0(0)))
    f4 = gw.submit(Request(budget=4, x0=_x0(1)))
    assert gw.pump() == 1                       # slots full: opens untimed
    assert gw.pump() == 1                       # leg 0..2 releases budget-2
    assert f2.done()
    x_late = _x0(2)
    f8 = gw.submit(Request(budget=8, x0=x_late))
    assert gw.pump() == 1                       # leg 2..4 releases 4, joins 8
    assert f4.done() and not f8.done()
    assert gw._traj is not None                 # extended past old target
    assert gw.pump() == 1                       # leg 4..8
    assert f8.done()
    np.testing.assert_array_equal(np.asarray(f8.result().latents),
                                  np.asarray(_direct([x_late], 8)[0]))
    # forwards: legs 2 + 2 + 4, plus the boundary-4 prefix for the joiner
    assert sampler.forwards == 8 + 4


def test_non_joinable_aged_request_flushes_between_legs():
    """A request whose budget is at or below the next boundary cannot join;
    once aged it rides a standalone flush batch interleaved with the legs."""
    gw, sampler, clock = _gateway(max_slots=2)
    big = [gw.submit(Request(budget=8, x0=_x0(i))) for i in range(2)]
    assert gw.pump() == 1                       # trajectory opens (full slots)
    f2 = gw.submit(Request(budget=2, x0=_x0(7)))
    assert gw.pump() == 1                       # leg 0..2; f2 young, no flush
    assert not f2.done()
    clock.advance(0.011)
    assert gw.pump() == 2                       # leg 2..4 AND the aged flush
    assert f2.done() and gw._traj is not None
    assert "continuous" not in f2.result().meta  # served by a flush batch
    gw.drain()
    assert all(f.done() for f in big)


def test_full_flush_bucket_dispatches_immediately_mid_flight():
    gw, sampler, clock = _gateway(max_slots=2, max_batch=2)
    big = [gw.submit(Request(budget=8, x0=_x0(i))) for i in range(2)]
    assert gw.pump() == 1                       # trajectory opens
    small = [gw.submit(Request(budget=2, x0=_x0(10 + i))) for i in range(2)]
    assert gw.pump() == 2                       # leg + full budget-2 bucket
    assert all(f.done() for f in small)
    gw.drain()
    assert all(f.done() for f in big)


def test_drain_completes_trajectory_and_queue():
    gw, sampler, clock = _gateway()
    futs = [gw.submit(Request(budget=b, x0=_x0(i)))
            for i, b in enumerate((8, 8, 4, 2, 2))]
    gw.drain()
    assert all(f.done() for f in futs)
    assert gw._traj is None and gw.queue.depth() == 0
    with pytest.raises(RuntimeError):
        gw.submit(Request(budget=2, x0=_x0(9)))


def test_slot_occupancy_accounting():
    """Occupancy is live slot-steps over the slot-steps paid for: each
    leg runs its live slots' power of two, not all ``max_slots``."""
    gw, sampler, clock = _gateway(max_slots=4)
    gw.submit(Request(budget=2, x0=_x0(0)))
    gw.submit(Request(budget=4, x0=_x0(1)))
    gw.drain()
    s = gw.stats()
    # leg 0..2: 2 live at width 2; leg 2..4: 1 live at width 1
    assert s["slot_occupancy"] == pytest.approx((2 * 2 + 1 * 2)
                                                / (2 * 2 + 1 * 2))
    assert s["legs"] == 2 and s["forwards"] == 4


def test_max_leg_clips_control_points_not_exits():
    """max_leg splits long legs so the host regains control, WITHOUT
    changing exits, forwards, or sample bits (the carry invariant holds
    across any leg partition)."""
    gw, sampler, clock = _gateway(max_slots=2, max_leg=1)
    x0s = [_x0(0), _x0(1)]
    futs = [gw.submit(Request(budget=b, x0=x))
            for b, x in zip((4, 8), x0s)]
    assert gw.pump() == 1                        # opens (slots full)
    for _ in range(8):                           # 8 single-step legs
        gw.pump()
    assert all(f.done() for f in futs)
    assert sampler.forwards == 8                 # legs add no forwards
    assert gw.stats()["legs"] == 8
    for f, b, x0 in zip(futs, (4, 8), x0s):
        np.testing.assert_array_equal(np.asarray(f.result().latents),
                                      np.asarray(_direct([x0], b)[0]))


def test_join_cost_cap_blocks_expensive_joins():
    """A join at boundary k costs k prefix forwards; the cap rejects joins
    whose prefix exceeds join_cost_cap * budget."""
    pending = [_entry(0, 8)]
    shape = (None, (2,))
    s = ContinuousScheduler(max_slots=4, boundaries=BUDGETS,
                            join_cost_cap=0.5)
    assert [e.uid for e in s.plan_joins(pending, 4, 4, shape)] == [0]
    tight = ContinuousScheduler(max_slots=4, boundaries=BUDGETS,
                                join_cost_cap=0.25)
    assert tight.plan_joins(pending, 4, 4, shape) == []      # 4 > 0.25 * 8
    assert [e.uid for e in tight.plan_joins(pending, 2, 4, shape)] == [0]
    with pytest.raises(ValueError):
        ContinuousScheduler(max_slots=4, boundaries=BUDGETS,
                            join_cost_cap=0.0)
    with pytest.raises(ValueError):
        ContinuousScheduler(max_slots=4, boundaries=BUDGETS, max_leg=0)


def test_trajectory_restart_outranks_mixed_flush():
    """When a trajectory retires with aged entries pending, the SAME pump
    opens the next trajectory from them — they must not leak into an
    unjoinable mixed flush batch."""
    gw, sampler, clock = _gateway(max_slots=2)
    first = [gw.submit(Request(budget=2, x0=_x0(i))) for i in range(2)]
    assert gw.pump() == 1                        # trajectory 1 opens
    # budget-2 entries cannot join at boundary 2 — only a restart serves them
    nxt = [gw.submit(Request(budget=2, x0=_x0(5 + i))) for i in range(2)]
    clock.advance(1.0)                           # everyone aged
    # leg 0..2 retires trajectory 1; trajectory 2 opens in the SAME pump
    assert gw.pump() == 2
    assert all(f.done() for f in first)
    assert gw._traj is not None
    assert gw.stats()["trajectories"] == 2
    gw.drain()
    assert all(f.done() for f in nxt)
    for f in nxt:
        assert f.result().meta["continuous"]     # served by a trajectory,
    assert gw.stats()["batches"] == 0            # never by a flush batch


def test_failed_leg_surfaces_into_slot_futures_and_engine_survives():
    """Regression: a sampler raising mid-leg (device OOM et al) must fail
    the occupied slots' futures and retire the trajectory — not strand the
    futures and kill the pump/serve thread."""
    class ExplodingLeg(CountingCarrySampler):
        def carry_extend(self, batch, carry, stop):
            raise RuntimeError("device boom")

    gw, _, clock = _gateway(ExplodingLeg(), max_slots=2)
    futs = [gw.submit(Request(budget=4, x0=_x0(i))) for i in range(2)]
    assert gw.pump() == 1                        # trajectory opens
    assert gw.pump() == 1                        # leg raises: funneled
    for f in futs:
        with pytest.raises(RuntimeError, match="device boom"):
            f.result(timeout=0)
    assert gw._traj is None and gw.stats()["failed"] == 2
    ok = gw.submit(Request(budget=4, x0=_x0(9)))     # engine still serves
    del ok
    gw.drain()                                   # drain terminates too


def test_failed_start_fails_starters_not_engine():
    class ExplodingStart(CountingCarrySampler):
        def carry_start(self, batch, x0):
            raise RuntimeError("init boom")

    gw, _, clock = _gateway(ExplodingStart(), max_slots=2)
    futs = [gw.submit(Request(budget=4, x0=_x0(i))) for i in range(2)]
    assert gw.pump() == 1
    for f in futs:
        with pytest.raises(RuntimeError, match="init boom"):
            f.result(timeout=0)
    assert gw._traj is None and gw.queue.depth() == 0


def test_failed_join_prefix_fails_joiners_but_trajectory_rolls_on():
    """A raising join-prefix dispatch reaches the joiners' futures (they
    already left the queue) while the in-flight slots keep integrating."""
    class ExplodingPrefix(CountingCarrySampler):
        def carry_extend(self, batch, carry, stop):
            # the join prefix is the only extend that starts from 0 while
            # a trajectory is past step 0
            if carry.step == 0 and self.forwards > 0:
                raise RuntimeError("prefix boom")
            return super().carry_extend(batch, carry, stop)

    gw, sampler, clock = _gateway(ExplodingPrefix(), max_slots=2)
    keeper = gw.submit(Request(budget=8, x0=_x0(0)))
    clock.advance(1.0)
    assert gw.pump() == 1                        # opens (aged)
    assert gw.pump() == 1                        # leg 0..2
    doomed = gw.submit(Request(budget=8, x0=_x0(1)))
    assert gw.pump() >= 1                        # leg 2..4 + failing join
    with pytest.raises(RuntimeError, match="prefix boom"):
        doomed.result(timeout=30)
    gw.drain()
    assert keeper.result(timeout=30).meta["served_budget"] == 8


def test_requires_carry_protocol():
    class NoCarry:
        budgets = (2, 4)

        def resolve_budget(self, m, strict=False):
            return m

    with pytest.raises(TypeError, match="carry"):
        ContinuousGateway(NoCarry())


def test_threaded_serve_forever_with_continuous_batching():
    sampler = CountingCarrySampler()
    gw = ContinuousGateway(sampler, max_slots=2, max_wait_ms=2.0)
    gw.start()
    futs = [gw.submit(Request(budget=b, x0=_x0(i)))
            for i, b in enumerate((2, 4, 8))]
    for f in futs:
        assert f.result(timeout=30).latents.shape == (2,)
    gw.shutdown()
    assert gw.stats()["completed"] == 3


# ---------------------------------------------------------------------------
# Carry protocol on the real smoke backbone
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def backbone():
    from repro.configs import get_config
    from repro.core.schedulers import fm_ot
    from repro.data.synthetic import DataConfig, SyntheticTokens
    from repro.models import model as M

    cfg = get_config("yi-6b", smoke=True)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    data = SyntheticTokens(cfg, DataConfig(batch_size=4, seq_len=8))
    art = SolverArtifact(
        spec=SolverSpec("midpoint", mode="anytime", budgets=(2, 4)),
        params=init_anytime(None, (2, 4), "nested"), val_psnr=0.0)

    def make_sampler():
        return AnytimeFlowSampler.from_artifact(
            art, params=params, cfg=cfg, sched=fm_ot())

    return cfg, data.batch(0), make_sampler


def test_backbone_carry_extend_matches_sample_all(backbone):
    """Leg-by-leg carry stepping reproduces the one-shot shared trajectory
    on the jit'd backbone path."""
    cfg, batch, make_sampler = backbone
    sampler = make_sampler()
    toks = batch["tokens"][:2]
    cond = {"tokens": toks}
    x0 = jax.random.normal(jax.random.PRNGKey(3), (2, 8, cfg.latent_dim))
    ref = sampler.sample_all_from(cond, x0)
    carry = sampler.carry_start(cond, x0)
    carry, exits2 = sampler.carry_extend(cond, carry, 2)
    carry, exits4 = sampler.carry_extend(cond, carry, 4)
    assert carry.step == 4
    np.testing.assert_allclose(np.asarray(exits2[2]), np.asarray(ref[2]),
                               atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(exits4[4]), np.asarray(ref[4]),
                               atol=1e-6, rtol=1e-6)


@pytest.mark.integration
def test_backbone_continuous_gateway_end_to_end(backbone):
    """Join on the real backbone: starters + a mid-flight joiner all match
    the direct per-budget sampler."""
    cfg, batch, make_sampler = backbone
    sampler = make_sampler()
    clock = FakeClock()
    gw = ContinuousGateway(sampler, max_slots=2, max_wait_ms=10.0,
                           clock=clock)
    toks = batch["tokens"][:3]
    x0 = jax.random.normal(jax.random.PRNGKey(5), (3, 8, cfg.latent_dim))
    f2 = gw.submit(Request(tokens=toks[0], budget=2, x0=x0[0]))
    f4 = gw.submit(Request(tokens=toks[1], budget=4, x0=x0[1]))
    assert gw.pump() == 1                        # opens (slots full)
    assert gw.pump() == 1                        # leg 0..2 releases budget-2
    late = gw.submit(Request(tokens=toks[2], budget=4, x0=x0[2]))
    assert gw.pump() == 1                        # leg 2..4 + join at 2? no:
    gw.drain()                                   # joiner needs budget > 2
    direct2 = sampler.sample_from({"tokens": toks[0][None]}, x0[:1], 2)
    direct4 = sampler.sample_from({"tokens": toks[1][None]}, x0[1:2], 4)
    direct4b = sampler.sample_from({"tokens": toks[2][None]}, x0[2:3], 4)
    np.testing.assert_allclose(np.asarray(f2.result().latents),
                               np.asarray(direct2[0]), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(f4.result().latents),
                               np.asarray(direct4[0]), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(late.result().latents),
                               np.asarray(direct4b[0]), atol=1e-5, rtol=1e-5)


@pytest.mark.integration
@pytest.mark.parametrize("budgets", [(2, 4), (4,)])
def test_backbone_sharded_continuous_matches_unsharded(backbone, budgets):
    """On the host mesh a leg narrows no further than the data axes'
    size, and the carry it leaves stays split along them; one live row
    is the narrowest case."""
    from repro.launch.mesh import make_host_mesh
    from repro.serving.sharded import data_axis_size

    cfg, batch, make_sampler = backbone
    ref_sampler = make_sampler()
    sampler = make_sampler()     # fresh: sharding re-places its params
    clock = FakeClock()
    mesh = make_host_mesh()
    gw = ContinuousGateway(sampler, max_slots=2, max_wait_ms=10.0,
                           mesh=mesh, clock=clock)
    n = len(budgets)
    toks = batch["tokens"][:n]
    x0 = jax.random.normal(jax.random.PRNGKey(7), (n, 8, cfg.latent_dim))
    futs = [gw.submit(Request(tokens=toks[i], budget=b, x0=x0[i]))
            for i, b in enumerate(budgets)]
    gw.pump(force=True)                          # opens
    gw.pump()                                    # leg 0..2
    width = max(gw.scheduler.join_bucket(n), data_axis_size(mesh))
    assert _leg_widths(gw) == {"0-2": [min(width, 2)]}
    if gw._traj is not None:
        assert gw._traj.carry.x.sharding.spec[0] == "data"
    gw.drain()
    for i, b in enumerate(budgets):
        ref = ref_sampler.sample_from({"tokens": toks[i:i + 1]},
                                      x0[i:i + 1], b)
        np.testing.assert_allclose(np.asarray(futs[i].result().latents),
                                   np.asarray(ref[0]), atol=1e-5, rtol=1e-5)


def test_plan_start_shape_groups_independent():
    """Satellite fix (PR 5): a full (or aged) slate of one shape must not
    wait behind an unaged singleton of another shape — the old plan gated
    the slate on the overall-oldest entry's shape (head-of-line blocking).
    Shape groups are now considered independently, oldest group first."""
    s = ContinuousScheduler(max_slots=2, boundaries=BUDGETS, max_wait_ms=10.0)

    def e(uid, shape, t=0.0):
        return _Entry(uid=uid, tokens=None, x0=jnp.zeros(shape),
                      requested=4, served=4, shape_key=(None, shape),
                      t_submit=t, future=None)

    lone_a = e(0, (3,))
    full_b = [e(1, (2,)), e(2, (2,))]
    # old behavior: the slate was gated on entry 0's shape -> nothing starts
    assert [x.uid for x in s.plan_start([lone_a, *full_b],
                                        now=0.005)] == [1, 2]
    # an AGED group behind the young singleton starts too
    aged_b = e(3, (2,), t=-0.02)
    assert [x.uid for x in s.plan_start([lone_a, aged_b],
                                        now=0.005)] == [3]
    # both shapes ready: the oldest group wins (FIFO across shapes)
    full_a = [e(5, (3,)), e(6, (3,))]
    assert [x.uid for x in s.plan_start([*full_b, *full_a],
                                        now=0.0)] == [1, 2]
    # force starts the oldest group even when nothing is ready
    assert [x.uid for x in s.plan_start([lone_a, e(9, (2,))],
                                        now=0.0, force=True)] == [0]
    # nothing ready, no force: still waits
    assert s.plan_start([lone_a], now=0.005) == []
