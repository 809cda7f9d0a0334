"""Serving telemetry layer: metrics registry, per-request tracing, and
the uniform export surfaces (ISSUE 8 acceptance).

Covers the registry primitives (deterministic histogram percentiles,
exact cross-host merge, Prometheus exposition), the one-schema property
across tiers (each ``stats()`` is a projection over a registry snapshot;
the fleet projects over the MERGE of per-host registries), per-request
trace reconstruction — including a fleet-routed STOLEN request's full
hop chain from a JSONL export — and thread-consistency of the counters.
"""
import json
import threading
import urllib.request

import jax
import numpy as np
import pytest
from fakeclock_sim import SKEW16, ParallelFleet, replay, stream

from repro.observability import (
    MetricsRegistry,
    NULL_RECORDER,
    TraceRecorder,
    bucket_bounds_at,
    format_stats_line,
    merge_snapshots,
    read_jsonl,
    to_prometheus,
)
from repro.observability.export import MetricsServer
from repro.serving import DrainTimeout, Gateway, Request
from repro.serving.toy import CountingToySampler, FakeClock


def _gateway(**kw):
    clock = FakeClock()
    sampler = CountingToySampler()
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_wait_ms", 10.0)
    gw = Gateway(sampler, clock=clock, **kw)
    return gw, sampler, clock


def _x0(i, shape=(2,)):
    return jax.random.normal(jax.random.PRNGKey(100 + i), shape)


# ---------------------------------------------------------------------------
# registry primitives
# ---------------------------------------------------------------------------


def test_registry_counter_gauge_histogram_snapshot():
    reg = MetricsRegistry()
    reg.counter("c", "a counter").inc(3)
    reg.gauge("g", "a gauge").set(7.5)
    reg.gauge("lazy", "callback gauge").set_fn(lambda: 11)
    h = reg.histogram("h", "a histogram")
    for v in (1.0, 2.0, 4.0):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["c"] == 3
    assert snap["g"] == 7.5
    assert snap["lazy"] == 11          # read at snapshot time
    assert snap["h"]["count"] == 3
    assert snap["h"]["sum"] == 7.0
    assert snap["h"]["max"] == 4.0
    assert snap["_meta"]["c"]["type"] == "counter"
    # same (name, labels) returns the same handle; kind mismatch raises
    assert reg.counter("c") is reg.counter("c")
    with pytest.raises(TypeError):
        reg.gauge("c")


def test_histogram_percentile_within_one_bucket_and_clamped_to_max():
    reg = MetricsRegistry()
    h = reg.histogram("w")
    rng = np.random.RandomState(0)
    vals = rng.uniform(0.5, 200.0, size=500)
    for v in vals:
        h.observe(float(v))
    for q in (50.0, 95.0, 99.0):
        got = h.percentile(q)
        exact = float(np.percentile(vals, q))
        lo, hi = bucket_bounds_at(h.bounds, h.buckets, q)
        assert abs(got - exact) <= (hi - lo) + 1e-9
        assert got <= h.max + 1e-12    # interpolation never exceeds max


def test_merge_snapshots_is_exact_and_rejects_bounds_mismatch():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("n").inc(2)
    b.counter("n").inc(5)
    vals_a, vals_b = [1.0, 3.0, 9.0], [2.0, 40.0]
    for v in vals_a:
        a.histogram("w").observe(v)
    for v in vals_b:
        b.histogram("w").observe(v)
    merged = merge_snapshots([a.snapshot(), b.snapshot()])
    assert merged["n"] == 7
    hist = merged["w"]
    assert hist["count"] == 5
    assert hist["sum"] == sum(vals_a) + sum(vals_b)
    assert hist["max"] == 40.0
    # merged percentile == percentile of one registry fed all values
    c = MetricsRegistry()
    for v in vals_a + vals_b:
        c.histogram("w").observe(v)
    assert merged["w"]["p95"] == c.snapshot()["w"]["p95"]
    bad = MetricsRegistry()
    bad.histogram("w", bounds=(1.0, 2.0)).observe(1.5)
    with pytest.raises(ValueError, match="bounds"):
        merge_snapshots([a.snapshot(), bad.snapshot()])


def test_prometheus_exposition_format():
    reg = MetricsRegistry()
    reg.counter("done", "completed things").inc(4)
    reg.counter("dispatches", labels={"program": "b4/k2"}).inc(2)
    reg.histogram("w", "waits", bounds=(1.0, 10.0)).observe(0.5)
    reg.histogram("w").observe(5.0)
    text = to_prometheus(reg.snapshot())
    assert "# HELP repro_done completed things" in text
    assert "# TYPE repro_done counter" in text
    assert "repro_done 4" in text
    assert 'repro_dispatches{program="b4/k2"} 2' in text
    # cumulative buckets + +Inf == count
    assert 'repro_w_bucket{le="1"} 1' in text
    assert 'repro_w_bucket{le="10"} 2' in text
    assert 'repro_w_bucket{le="+Inf"} 2' in text
    assert "repro_w_count 2" in text


# ---------------------------------------------------------------------------
# one schema across tiers: stats() is a projection over the registry
# ---------------------------------------------------------------------------


def test_gateway_stats_is_projection_over_registry():
    gw, sampler, clock = _gateway()
    futs = [gw.submit(Request(budget=2, x0=_x0(i))) for i in range(4)]
    clock.advance(1.0)
    while gw.pump():
        pass
    assert all(f.done() for f in futs)
    s = gw.stats()
    snap = gw.metrics_snapshot()
    assert s["completed"] == snap["completed"] == 4
    # wait histogram count == settled (observed exactly where completed
    # increments) — the invariant the CI benches gate
    assert snap["wait_ms"]["count"] == s["completed"]
    assert s["wait_p95_ms"] == snap["wait_ms"]["p95"]
    assert s["jit_programs"] >= 1
    # disabled tracing is the default: no trace on the response
    assert all(f.result().trace is None for f in futs)
    assert not NULL_RECORDER


def test_response_trace_opt_in_records_lifecycle():
    rec = TraceRecorder()
    gw, sampler, clock = _gateway(recorder=rec)
    f_traced = gw.submit(Request(budget=2, x0=_x0(0), trace=True))
    f_plain = gw.submit(Request(budget=2, x0=_x0(1)))
    clock.advance(1.0)
    while gw.pump():
        pass
    names = [e["event"] for e in f_traced.result().trace]
    assert names == ["submit", "dispatch", "settle"]
    dispatch = f_traced.result().trace[1]
    assert dispatch["program"].startswith("b")
    assert f_plain.result().trace is None   # opt-in is per request
    # the recorder still saw BOTH requests (trace= only gates the echo)
    assert len(rec.trace(f_plain.uid)) == 3
    assert rec.open_spans() == {}


def test_zoo_stats_is_view_over_registry_counters():
    from repro.serving import SolverZoo
    from repro.solvers import SolverArtifact, SolverSpec
    from repro.core.anytime import init_anytime

    reg = MetricsRegistry()
    zoo = SolverZoo(capacity=2, metrics=reg)
    art = SolverArtifact(
        spec=SolverSpec("midpoint", mode="anytime", budgets=(2, 4)),
        params=init_anytime(None, (2, 4)), val_psnr=0.0)
    zoo.put(art)
    zoo.get(art.spec)
    assert zoo.stats.hits == 1 and zoo.stats.misses == 0
    assert reg.snapshot()["zoo_hits"] == 1


def test_page_allocator_gauges_ride_the_registry():
    from repro.serving.decode import PageAllocator

    reg = MetricsRegistry()
    alloc = PageAllocator(9)           # page 0 reserved -> 8 usable
    alloc.bind(reg)
    held = alloc.alloc(1)
    held += alloc.alloc(3)
    snap = reg.snapshot()
    assert snap["pages_in_use"] == alloc.in_use == 4
    assert snap["peak_pages"] == alloc.peak == 4
    assert snap["page_pool_total"] == 8
    alloc.free(held[1:])
    assert reg.snapshot()["pages_in_use"] == 1    # lazy: reads live state
    assert reg.snapshot()["peak_pages"] == 4      # high-water sticks


# ---------------------------------------------------------------------------
# drain diagnostics + thread consistency (satellites 2, 3)
# ---------------------------------------------------------------------------


def test_drain_timeout_carries_registry_snapshot_and_open_spans():
    rec = TraceRecorder()
    gw, sampler, clock = _gateway(recorder=rec)
    gw.submit(Request(budget=2, x0=_x0(0)))
    entry = gw.queue.snapshot()
    gw._take(entry)            # wedge: in flight, future never resolves
    with pytest.raises(DrainTimeout) as err:
        gw.drain(timeout=0.05)
    assert err.value.snapshot["submitted"] == 1
    assert err.value.snapshot["inflight"] == 1
    uid = entry[0].uid
    assert uid in err.value.spans   # never settled -> span still open
    assert [e["event"] for e in err.value.spans[uid]] == ["submit"]
    gw._settle(1)
    entry[0].future.set_result(None)
    gw.drain(timeout=5.0)


def test_counters_consistent_under_concurrent_hammer():
    """Threads hammer submit / stats() / pump concurrently: every stats()
    cut must be internally consistent (settled <= submitted, counters
    monotone) and the final histogram count must equal completions."""
    gw, sampler, clock = _gateway(max_batch=4)
    clock.advance(1.0)
    stop = threading.Event()
    errors = []

    def submitter(base):
        for i in range(40):
            gw.submit(Request(budget=2, x0=_x0(base + i)))

    def pumper():
        while not stop.is_set():
            gw.pump()
            clock.advance(0.05)

    def watcher():
        last_submitted = last_completed = 0
        while not stop.is_set():
            s = gw.stats()
            if s["completed"] > s["submitted"]:
                errors.append(f"completed {s['completed']} > "
                              f"submitted {s['submitted']}")
            if (s["submitted"] < last_submitted
                    or s["completed"] < last_completed):
                errors.append("counter went backwards")
            last_submitted, last_completed = s["submitted"], s["completed"]

    threads = ([threading.Thread(target=submitter, args=(100 * k,))
                for k in range(3)]
               + [threading.Thread(target=pumper),
                  threading.Thread(target=watcher)])
    for t in threads:
        t.start()
    for t in threads[:3]:
        t.join()
    gw.drain(timeout=30.0)
    stop.set()
    for t in threads[3:]:
        t.join()
    assert not errors, errors
    s = gw.stats()
    assert s["submitted"] == s["completed"] == 120
    assert gw.metrics_snapshot()["wait_ms"]["count"] == 120


# ---------------------------------------------------------------------------
# fleet: merged registries + stolen-request hop reconstruction
# ---------------------------------------------------------------------------


def test_fleet_stats_equal_merge_of_host_registries():
    events = stream(SKEW16, 24, inter_ms=2.0, burst=8)
    run = replay(lambda clock, tick: ParallelFleet(clock), events)
    stats, snap = run.stats, run.snapshot
    assert stats["completed"] == 24
    assert snap["wait_ms"]["count"] == 24     # merge sums host histograms
    assert stats["wait_p95_ms"] == snap["wait_ms"]["p95"]
    assert stats["hosts"] == 4
    assert sum(stats["routed"].values()) == 24


def test_stolen_request_hop_chain_reconstructable_from_jsonl(tmp_path):
    """The headline tracing acceptance: run the skewed fleet workload with
    stealing, export the trace to JSONL, and reconstruct a STOLEN
    request's full hop sequence — submit -> route (home host) -> steal
    (leaves home) -> inject (lands on thief) -> dispatch -> settle, with
    the dispatch host differing from the routed home."""
    from repro.serving import WorkStealer

    rec = TraceRecorder()
    events = stream(SKEW16, 48, inter_ms=2.0, burst=8)
    stealer = WorkStealer(min_queue=8, max_steal=4)
    stats = replay(lambda clock, tick: ParallelFleet(clock, stealer, rec),
                   events).stats
    assert stats["steals"] > 0
    path = tmp_path / "trace.jsonl"
    n = rec.export_jsonl(str(path))
    assert n == len(read_jsonl(str(path)))

    by_uid = {}
    for e in read_jsonl(str(path)):
        by_uid.setdefault(e["uid"], []).append(e)
    stolen = {uid: evs for uid, evs in by_uid.items()
              if any(e["event"] == "steal" for e in evs)}
    assert len(stolen) == stats["steals"]
    for uid, evs in stolen.items():
        names = [e["event"] for e in evs]
        assert names == ["submit", "route", "steal", "inject",
                         "dispatch", "settle"], (uid, names)
        hop_host = {e["event"]: e["host"] for e in evs}
        assert hop_host["steal"] == hop_host["route"]    # left its home
        assert hop_host["inject"] != hop_host["steal"]   # landed elsewhere
        assert hop_host["dispatch"] == hop_host["inject"]
        assert evs[-1]["status"] == "completed"
    # every request settled exactly once, stolen or not
    settles = [e for evs in by_uid.values() for e in evs
               if e["event"] == "settle"]
    assert len(settles) == 48


# ---------------------------------------------------------------------------
# export surfaces
# ---------------------------------------------------------------------------


def test_format_stats_line_renders_tier_segments():
    base = {"completed": 4, "submitted": 5, "queue_depth": 1, "batches": 2,
            "mixed_batches": 0, "forwards": 8, "nfe_per_request": 2.0,
            "occupancy": 0.9, "wait_p50_ms": 1.0, "wait_p95_ms": 2.0,
            "max_wait_ms": 3.0, "throughput_rps": 10.0}
    line = format_stats_line(base, prefix="gw")
    assert line.startswith("gw: done=4/5 q=1")
    assert "fleet" not in line and "traj=" not in line
    fleet_line = format_stats_line(
        dict(base, hosts=2, steals=3, steal_rounds=1, rerouted=0,
             routed={"h0": 3, "h1": 2}))
    assert "fleet hosts=2 steals=3" in fleet_line
    assert "routed: h0=3 h1=2" in fleet_line
    decode_line = format_stats_line(
        dict(base, tokens_out=20, tokens_per_s=5.0, slot_occupancy=0.8,
             joins=2, prefill_calls=3, cancelled=0, page_size=8,
             pages_in_use=4, peak_pages=6, peak_kv_per_slot=12.0))
    assert "tokens=20 tok/s=5.0" in decode_line
    assert "paged page_size=8" in decode_line


def test_metrics_server_serves_prometheus_and_json():
    gw, sampler, clock = _gateway()
    f = gw.submit(Request(budget=2, x0=_x0(0)))
    clock.advance(1.0)
    while gw.pump():
        pass
    assert f.done()
    srv = MetricsServer(gw.metrics_snapshot, port=0).start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        body = urllib.request.urlopen(f"{base}/metrics.json").read()
        snap = json.loads(body)
        assert snap["completed"] == 1
        text = urllib.request.urlopen(f"{base}/metrics").read().decode()
        assert "# TYPE repro_completed counter" in text
        assert "repro_completed 1" in text
        with pytest.raises(Exception):
            urllib.request.urlopen(f"{base}/nope")
    finally:
        srv.stop()
