"""CPU tests of the readers of the serving thread's spans and of the
rows each dispatch carried: ``flow.host_busy``, ``flow.host_tick_max_ms``
and ``flow.backbone_bound`` on synthetic runs."""
import json
import os

import pytest

from bench import run as bench_run
from bench import spans, work
from bench.blocks import gqa_swiglu
from bench.model import Model

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def _json(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def _run(trace_spans=None, counters=None, traced=True, flow_s=2.0):
    return {"mix": _json("bench/traffic/flow_mixed.json"),
            "model": Model(_json("bench/configs/yi-6b.json")),
            "peaks": _json("bench/peaks.json")["kinds"]["TPU v5 lite"],
            "counters": dict(counters or {}),
            "trace": ({"profile_s": 10.0, "programs": {"flow": flow_s},
                       "spans": list(trace_spans or [])}
                      if traced else None)}


# two ticks: the first waits 0.5 s on a leg's exits, the second on two
# readbacks; a leg, a join and an idle poll are not subtracted
TICKS = [("gateway.pump", 0.0, 1.0),
         ("continuous.leg.0-4", 0.05, 0.1),
         ("continuous.sync.0-4", 0.2, 0.7),
         ("continuous.release.4", 0.7, 0.8),
         ("gateway.idle", 1.0, 1.001),
         ("gateway.pump", 2.0, 2.5),
         ("continuous.join.4/k2", 2.05, 2.08),
         ("gateway.sync.b4/k1", 2.1, 2.2),
         ("continuous.sync.4-8", 2.3, 2.35)]


def test_pump_self_time_subtracts_nested_syncs_only():
    assert spans.pump_self_times(TICKS) == pytest.approx([0.5, 0.35])
    # a sync outside every pump (another thread's readback) is not a child
    outside = TICKS + [("decode.sync.k4", 3.0, 4.0)]
    assert spans.pump_self_times(outside) == pytest.approx([0.5, 0.35])


def test_host_busy_and_tick_max_read_the_self_times():
    busy = bench_run.reader("flow.host_busy")
    tick = bench_run.reader("flow.host_tick_max_ms")
    run = _run(TICKS)
    assert busy(run) == pytest.approx(100 * 0.85 / 10.0)
    assert tick(run) == pytest.approx(500.0)


def test_backbone_bound_weight_floor_decides_at_one_row_not_eight():
    read = bench_run.reader("flow.backbone_bound")
    model = Model(_json("bench/configs/yi-6b.json"))
    peaks = _json("bench/peaks.json")["kinds"]["TPU v5 lite"]
    row_s = work.flow_request_flops(model, 1, 64, True) / peaks[
        "bf16_flop_per_s"]
    weight_s = (2 * (32 * gqa_swiglu.layer_params(model.c) + 2 * 64 * 4096)
                / peaks["hbm_bytes_per_s"])
    # yi-6b: about 11.1 GB of matrices, 13.5 ms a step; a row 7.2 ms
    assert weight_s == pytest.approx(0.0135, rel=0.01)
    assert row_s == pytest.approx(0.0072, rel=0.01)
    one = read(_run([], {'forwards_by_rows{rows="1"}': 10}))
    assert one == pytest.approx(100 * 10 * weight_s / 2.0)
    eight = read(_run([], {'forwards_by_rows{rows="8"}': 10}))
    assert eight == pytest.approx(100 * 10 * 8 * row_s / 2.0)
    both = read(_run([], {'forwards_by_rows{rows="1"}': 10,
                          'forwards_by_rows{rows="8"}': 10,
                          "forwards": 40}))
    assert both == pytest.approx(one + eight)
    # no device time of the flow programs: nothing to divide by
    assert read(_run([], {'forwards_by_rows{rows="1"}': 10},
                     flow_s=0.0)) is None


@pytest.mark.parametrize("name", ["flow.host_busy", "flow.host_tick_max_ms",
                                  "flow.backbone_bound"])
def test_readers_say_nothing_without_their_spans_or_counters(name):
    """A program that names no tick and counts no rows (the parent of
    these metrics) reads None, and never raises."""
    read = bench_run.reader(name)
    assert read(_run(traced=False)) is None
    bare = _run([("continuous.leg.0-4", 1.0, 1.1),
                 ("gateway.dispatch.b4/k2", 2.0, 2.1)],
                {"forwards": 40, "join_forwards": 8})
    assert read(bare) is None
