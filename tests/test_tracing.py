"""Serving-thread tracing: every phase of a tick in a named profiler span
with its row counts as the event's stats, the waits on device results
split from the enqueues (``device_wait_ms`` / ``device_dispatch_ms``),
the ``forwards_by_rows`` counter of real rows per NFE step, and the
process-wide ``compilations`` gauge."""
import glob
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serving import ContinuousGateway, Gateway, Request
from repro.serving.decode import DecodeGateway, DecodeRequest
from repro.serving.slo import SLOConfig
from repro.serving.toy import CountingToySampler, FakeClock, ToyDecodeEngine

BUDGETS = (2, 4, 8)
SLOTS = 2


def _x0(i, shape=(2,)):
    return jax.random.normal(jax.random.PRNGKey(200 + i), shape)


def _rows_counts(gw) -> dict:
    """``{rows: NFE steps}`` of the ``forwards_by_rows`` counter."""
    out = {}
    for key, v in gw.metrics.snapshot().items():
        m = re.fullmatch(r'forwards_by_rows\{rows="(\d+)"\}', key)
        if m:
            out[int(m.group(1))] = v
    return out


# -- the tick script, shared by the profiled and the fake-clock runs ---------


def _joins_and_flushes(gw, clock, sub):
    """Start a 2-slot trajectory, join a budget-8 request at boundary 2,
    flush a full budget-2 batch and a mixed 4/8 batch, run to the end.
    ``sub(i, budget, **kw)`` submits request i; ticks go through the
    serving loop's ``_tick`` (the ``gateway.pump`` span)."""
    futs = [sub(0, 2), sub(1, 8), sub(2, 8)]
    gw._tick()           # opens [0, 1]; 2 waits (young, bucket not full)
    gw._tick()           # leg 0-2 releases 0; 2 joins at boundary 2
    futs += [sub(3, 2), sub(4, 2), sub(5, 4), sub(6, 8)]
    clock.advance(1.0)
    gw._tick()           # leg 2-4; flushes b2/k2 and a mixed 4/8 batch
    gw._tick()           # leg 4-8 releases 1 and 2
    assert all(f.done() for f in futs)
    return futs


def _preempt_and_resume(gw, clock, sub):
    """Two budget-8 residents; an urgent budget-4 request preempts one at
    boundary 2, which resumes from its saved carry at boundary 4."""
    lows = [sub(10, 8), sub(11, 8)]
    gw._tick(force=True)
    hot = sub(12, 4, priority=1)
    gw._tick()           # leg 0-2: preempt, urgent fresh join
    gw._tick()           # leg 2-4: urgent exits; victim resumes 2-4
    gw._tick()           # leg 4-8: both lows exit
    assert all(f.done() for f in lows + [hot])
    return lows + [hot]


# -- profiler spans -----------------------------------------------------------


@pytest.fixture(scope="module")
def backbone_sampler():
    from repro.configs import get_config
    from repro.core.anytime import init_anytime
    from repro.core.schedulers import fm_ot
    from repro.models import model as M
    from repro.serving import AnytimeFlowSampler

    cfg = get_config("yi-6b", smoke=True)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    sampler = AnytimeFlowSampler(
        params=params, cfg=cfg, sched=fm_ot(),
        anytime=init_anytime(None, BUDGETS), budgets=BUDGETS, cfg_scale=1.5)
    return cfg, sampler


def _profile(tmp_path, fn):
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("continuous.", "gateway.")):
                    spans.append((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns,
                                  dict(e.stats)))
    return sorted(spans, key=lambda sp: sp[1])


def test_profiler_names_every_phase_of_the_tick(backbone_sampler, tmp_path):
    """Under the profiler, the scripted ticks record every span of the
    serving thread with its rows / live / bucket stats; in a short run
    of the real serving thread every non-idle span lies inside a
    ``gateway.pump``. Each readback (a ``*.sync.*`` span) is one
    ``device_wait_ms`` observation, each dispatch one
    ``device_dispatch_ms``."""
    cfg, sampler = backbone_sampler
    gws = []

    def make(**kw):
        clock = kw.pop("clock", None)
        gw = ContinuousGateway(sampler, max_slots=SLOTS, max_wait_ms=10.0,
                               clock=clock, **kw)
        gws.append(gw)
        return gw

    def submitter(gw):
        def sub(i, budget, **kw):
            toks = jnp.full((8,), i % cfg.vocab, jnp.int32)
            x0 = jax.random.normal(jax.random.PRNGKey(i),
                                   (8, cfg.latent_dim))
            return gw.submit(Request(tokens=toks, budget=budget, x0=x0,
                                     **kw))
        return sub

    def script():
        clock = FakeClock()
        gw = make(clock=clock)
        _joins_and_flushes(gw, clock, submitter(gw))
        clock = FakeClock()
        gw = make(clock=clock, slo=SLOConfig())
        _preempt_and_resume(gw, clock, submitter(gw))
        # the real serving thread, on the real clock
        gw = make()
        sub = submitter(gw)
        gw.start(poll_s=0.001)
        futs = []
        for i, b in enumerate((2, 8, 4, 8, 2, 4)):
            futs.append(sub(20 + i, b))
            time.sleep(0.01)
        for f in futs:
            f.result(60)
        gw.stop()

    spans = _profile(tmp_path, script)
    names = [n for n, *_ in spans]
    args = {}
    for n, _, _, st in spans:
        args.setdefault(n, st)
    expected = {
        "gateway.pump": {}, "gateway.idle": {}, "continuous.plan": {},
        "continuous.assemble": {"rows": 2, "bucket": 2},
        "continuous.leg.0-2": {"live": 2, "bucket": 2},
        "continuous.sync.0-2": {},
        "continuous.release.2": {"rows": 1},
        "continuous.join.2/k1": {"rows": 1},
        "continuous.scatter.2": {"rows": 1},
        "continuous.leg.2-4": {"live": 2, "bucket": 2},
        "continuous.sync.preempt.2": {},
        "continuous.resume.2-4/k1": {"rows": 1},
        "gateway.assemble": {"rows": 2, "bucket": 2},
        "gateway.dispatch.b2/k2": {"rows": 2, "bucket": 2},
        "gateway.sync.b2/k2": {},
        "gateway.dispatch.bmix/k2": {"rows": 2, "bucket": 2},
        "gateway.sync.bmix/k2": {},
        "gateway.settle": {"rows": 2},
        "continuous.sync.4-8": {},
        "continuous.release.8": {"rows": 2},
    }
    for name, stats in expected.items():
        assert name in args, (name, sorted(set(names)))
        for k, v in stats.items():
            assert int(args[name][k]) == v, (name, k, args[name])
    # every non-idle span of the serving thread lies inside one tick
    pumps = [(a, b) for n, a, b, _ in spans if n == "gateway.pump"]
    for n, a, b, _ in spans:
        if n not in ("gateway.pump", "gateway.idle"):
            assert any(pa <= a and b <= pb for pa, pb in pumps), n
    # one wait observation per readback, one dispatch per enqueue
    waits = sum(gw.metrics.snapshot()["device_wait_ms"]["count"]
                for gw in gws)
    dispatches = sum(gw.metrics.snapshot()["device_dispatch_ms"]["count"]
                     for gw in gws)
    assert waits == sum(".sync." in n for n in names)
    assert dispatches == sum(n.startswith(("continuous.leg.",
                                           "gateway.dispatch."))
                             for n in names)
    for gw in gws:
        snap = gw.metrics.snapshot()
        assert snap["host_assembly_ms"]["count"] > 0


# -- forwards_by_rows ---------------------------------------------------------


@pytest.mark.parametrize("script,slo", [(_joins_and_flushes, None),
                                        (_preempt_and_resume, SLOConfig())])
def test_forwards_by_rows_sum_to_the_served_budgets(script, slo):
    """On the fake clock, with mixed budgets, joins, flushes and a
    preempted resume: the real rows of every NFE step dispatched add up
    to the served budgets of the completed requests, exactly (padding
    and steps no row needs never count), and no more steps are counted
    than forwards were spent."""
    clock = FakeClock()
    sampler = CountingToySampler(budgets=BUDGETS)
    gw = ContinuousGateway(sampler, max_slots=SLOTS, max_wait_ms=10.0,
                           clock=clock, slo=slo)

    def sub(i, budget, **kw):
        return gw.submit(Request(budget=budget, x0=_x0(i), **kw))

    futs = script(gw, clock, sub)
    by_rows = _rows_counts(gw)
    served = sum(f.result().meta["served_budget"] for f in futs)
    assert sum(r * n for r, n in by_rows.items()) == served
    s = gw.stats()
    assert sum(by_rows.values()) <= s["forwards"] == sampler.forwards
    assert s["completed"] == len(futs)
    if slo is not None:
        assert s["preemptions"] == 1


def test_forwards_by_rows_of_a_mixed_flush_follow_the_exits():
    """A mixed flush of budgets 2, 4 and 8 runs 8 steps for three rows:
    steps 0-1 are needed by 3 rows, 2-3 by 2, 4-7 by 1."""
    clock = FakeClock()
    gw = Gateway(CountingToySampler(budgets=BUDGETS), max_batch=4,
                 max_wait_ms=10.0, mixed_budget_policy="always", clock=clock)
    for i, b in enumerate((2, 4, 8)):
        gw.submit(Request(budget=b, x0=_x0(i)))
    gw.pump(force=True)
    assert _rows_counts(gw) == {3: 2, 2: 2, 1: 4}
    assert gw.stats()["forwards"] == 8


# -- enqueue vs wait ----------------------------------------------------------


class _TickingSampler(CountingToySampler):
    """Each forward advances the fake clock 5 ms, inside the dispatch."""

    def __init__(self, clock):
        super().__init__(budgets=BUDGETS)
        self._clock = clock

    def on_forward(self):
        super().on_forward()
        self._clock.advance(0.005)


def test_fake_clock_dispatch_keeps_the_simulated_time_wait_reads_zero():
    clock = FakeClock()
    gw = Gateway(_TickingSampler(clock), max_batch=2, max_wait_ms=10.0,
                 clock=clock)
    gw.submit(Request(budget=4, x0=_x0(0)))
    gw.pump(force=True)
    snap = gw.metrics.snapshot()
    assert snap["device_dispatch_ms"]["count"] == 1
    assert snap["device_dispatch_ms"]["sum"] == pytest.approx(20.0)
    assert snap["device_wait_ms"]["count"] == 1
    assert snap["device_wait_ms"]["sum"] == 0.0
    # the SLO cost model reads enqueue + wait + assembly
    assert gw._dispatch_cost_ms() == pytest.approx(20.0)


def test_decode_step_splits_enqueue_from_readback():
    """One ``device_wait_ms`` per decode step (its token readback); the
    prefill calls are enqueues only."""
    clock = FakeClock()
    gw = DecodeGateway(ToyDecodeEngine(on_step=lambda: clock.advance(0.001)),
                       max_slots=2, cache_slots=32, clock=clock)
    futs = [gw.submit(DecodeRequest(prompt=np.arange(1, 6), max_tokens=3))
            for _ in range(2)]
    gw.drain()
    assert all(f.done() for f in futs)
    snap = gw.metrics.snapshot()
    steps, prefills = snap["batches"], snap["prefill_calls"]
    assert steps > 0 and prefills > 0
    assert snap["device_wait_ms"]["count"] == steps
    assert snap["device_wait_ms"]["sum"] == 0.0
    assert snap["device_dispatch_ms"]["count"] == steps + prefills


# -- compilations -------------------------------------------------------------


def test_compilations_gauge_counts_a_retrace_under_one_label():
    """Every gateway reads the one process-wide compile listener; a new
    input shape compiles the same jitted function again, which the
    gauge sees while ``jit_programs`` (labels) cannot."""
    a = Gateway(CountingToySampler(budgets=BUDGETS), clock=FakeClock())
    b = Gateway(CountingToySampler(budgets=BUDGETS), clock=FakeClock())
    before = a.metrics.snapshot()["compilations"]
    f = jax.jit(lambda x: x * 3 + 1)
    f(jnp.ones((3, 5)))
    f(jnp.ones((7, 5)))
    after = a.metrics.snapshot()["compilations"]
    assert after >= before + 2
    assert b.metrics.snapshot()["compilations"] == after
