"""CPU tests of the benchmark's yardstick: the trace reduction, the work
counts, the configuration files and the weights, the traffic generator,
the contract of ``BENCHMARK.json``, one ``--smoke`` run of each cell, and
the comparison that decides ``correct`` (a timed path broken underneath
must read false; the control must read far above the program)."""
import contextlib
import dataclasses
import io
import json
import os
import re

import numpy as np
import pytest

from bench import loadgen, trace, work
from bench.blocks import gqa_swiglu
from bench.model import Model

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
TESTDATA = os.path.join(ROOT, "bench", "testdata")


def _json(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def _yi6b(smoke=False):
    return Model(_json("bench/configs/yi-6b.json"), smoke=smoke)


CELLS = [w["name"] for w in _json("BENCHMARK.json")["workloads"]]


# -- trace reduction -----------------------------------------------------------


@dataclasses.dataclass
class Ev:
    name: str
    start_ns: int
    duration_ns: int
    stats: tuple = ()


@dataclasses.dataclass
class Line:
    name: str
    events: list


@dataclasses.dataclass
class Plane:
    name: str
    lines: list
    stats: tuple = ()


@dataclasses.dataclass
class Data:
    planes: list


def _synthetic():
    ms = 1_000_000
    mods = [Ev("jit__step_slots(1)", 0, 10 * ms),
            Ev("jit__prefill(2)", 30 * ms, 20 * ms),
            Ev("jit__step_slots(1)", 60 * ms, 10 * ms)]
    ops = [Ev("%while.3 = (...) while(...)", 0, 10 * ms),
           Ev("%fusion.1 = bf16[8] fusion(...)", 0, 4 * ms),
           Ev("%paged_attention.2 = bf16[8] custom-call(...)", 4 * ms, 6 * ms),
           Ev("%fusion.1 = bf16[8] fusion(...)", 30 * ms, 20 * ms),
           Ev("%paged_attention.2 = bf16[8] custom-call(...)", 35 * ms,
              5 * ms),
           Ev("%paged_attention.2 = bf16[8] custom-call(...)", 60 * ms,
              10 * ms)]
    host = [Ev("decode.step.k4", 0, 2 * ms),
            Ev("decode.prefill.w4", 12 * ms, 30 * ms),
            Ev("decode.step.k4", 58 * ms, 1 * ms),
            Ev("unrelated", 0, 100 * ms)]
    return Data([
        Plane("/device:TPU:0", [Line("XLA Modules", mods),
                                Line("XLA Ops", ops)]),
        Plane("/host:CPU", [Line("python3", host)]),
        Plane("Task Environment", [], (("profile_start_time", 0),
                                       ("profile_stop_time", 100 * ms)))])


def test_trace_reduce_synthetic_busy_gaps_programs_kernels():
    r = trace.reduce(None, {"step": ("jit__step_slots",),
                            "prefill": ("jit__prefill",)},
                     {"paged_attention": ("paged_attention",)},
                     data=_synthetic())
    assert r["busy_s"] == pytest.approx(0.040)          # 0-10, 30-50, 60-70
    assert r["profile_s"] == pytest.approx(0.100)
    assert r["programs"] == pytest.approx({"step": 0.020, "prefill": 0.020})
    assert r["program_calls"] == {"step": 2, "prefill": 1}
    assert r["kernels"]["paged_attention"] == pytest.approx(
        {"step": 0.016, "prefill": 0.005})
    # two idle gaps of 20 ms and 10 ms, keyed by the innermost host span
    assert r["gaps"] == [("decode.prefill.w4", pytest.approx(0.020)),
                         ("after decode.prefill.w4", pytest.approx(0.010))]
    assert [s[0] for s in r["spans"]] == ["decode.step.k4",
                                          "decode.prefill.w4",
                                          "decode.step.k4"]
    ops = dict(r["breakdown"]["device_ops"])
    assert ops == pytest.approx({"fusion": 0.024, "paged_attention": 0.021})


def test_trace_reduce_recorded_chip_trace():
    """A trace recorded on a TPU v5e (``testdata/record.py``): a few
    paged decode steps, one prefill and a flow sample at small sizes."""
    path = os.path.join(TESTDATA, "v5e_small.xplane.pb")
    meta = _json("bench/testdata/v5e_small.json")
    r = trace.reduce(path, {"step": ("jit__step_slots",),
                            "prefill": ("jit__prefill",),
                            "flow": ("jit__sample",)},
                     {"paged_attention": ("paged_attention",)})
    assert r["devices"] == 1
    assert r["program_calls"] == meta["program_calls"]
    assert 0 < r["busy_s"] <= r["profile_s"]
    assert r["kernels"]["paged_attention"].get("step", 0) > 0
    assert r["kernels"]["paged_attention"].get("prefill", 0) > 0
    assert sorted({s[0] for s in r["spans"]}) == sorted(meta["spans"])
    for fam, t in r["programs"].items():
        assert 0 < t < r["profile_s"], fam


# -- work counts -----------------------------------------------------------------


def test_work_hand_counts_yi6b():
    model = _yi6b()
    c = model.c
    assert model.stack is gqa_swiglu
    # attention 4096*4096*2 + 2*4096*512, MLP 3*4096*11008
    assert gqa_swiglu.attn_params(c) == 37_748_736
    assert gqa_swiglu.mlp_params(c) == 135_266_304
    assert 32 * gqa_swiglu.layer_params(c) == 5_536_481_280
    assert gqa_swiglu.position_params(c) == 5_536_481_280
    # per position: 2 * (blocks + proj_in + proj_out) + causal attention
    per_pos = 2 * (5_536_481_280 + 2 * 64 * 4096)
    attn = 4 * 32 * 32 * 128 * (64 * 65 / 2)
    assert gqa_swiglu.causal_attn_flops(c, 64, 0) == attn
    assert work.flow_forward_flops(model, 1, 64) == pytest.approx(
        64 * per_pos + attn + 4 * 4096 ** 2)
    # a budget-8 guided sample: 8 steps x 2 forwards, no time-MLP per row
    assert work.flow_request_flops(model, 8, 64, True) == pytest.approx(
        16 * (64 * per_pos + attn))
    # unguided: one forward a step
    assert work.flow_request_flops(model, 8, 64, False) == pytest.approx(
        8 * (64 * per_pos + attn))


@pytest.mark.parametrize("tokens", [1, 128, 2048])
def test_work_weight_floor_yi6b_whatever_the_tokens(tokens):
    """A dense stack reads every matrix once whatever the step holds:
    11.07 GB of bf16 blocks and latent projections for yi-6b."""
    model = _yi6b()
    assert gqa_swiglu.matrix_bytes(model.c, tokens) == 2 * 5_536_481_280
    assert work.flow_weight_bytes(model, tokens) == 11_074_011_136


def test_least_time_never_above_what_the_chip_can_do():
    """Shares built on these counts stay under 100% for a step the chip
    ran at its measured best: a guided yi-6b step over 16 x 64 rows took
    132 ms on a v5e (PERF.md), and the least time must be below it."""
    peaks = _json("bench/peaks.json")["kinds"]["TPU v5 lite"]
    least = 16 * work.flow_request_flops(_yi6b(), 1, 64, True) / peaks[
        "bf16_flop_per_s"]
    assert 0.05 < least < 0.132


# -- configuration files and weights ---------------------------------------

# the program's qwen3-moe-30b-a3b with 8 of its 128 experts, in a file
MOE_DOC = {"name": "qwen3-moe-test", "arch": "qwen3-moe-30b-a3b",
           "blocks": "gqa_swiglu",
           "model": {"n_layers": 4, "moe": {"num_experts": 8}},
           "smoke": {"n_layers": 2, "d_model": 128, "n_heads": 4,
                     "n_kv_heads": 2, "head_dim": 32, "vocab": 256,
                     "moe": {"d_expert": 64}, "dtype": "float32"}}
PINS = os.path.join(TESTDATA, "yi6b_smoke_pins.json")


def test_config_nested_moe_builds_the_program_sub_config():
    """A nested ``moe`` replaces the program's ``MoEConfig`` field by
    field: the file's sizes, the program's other fields."""
    from repro.configs import MoEConfig, get_config

    cfg = Model(MOE_DOC).program_config()
    base = get_config("qwen3-moe-30b-a3b")
    assert isinstance(cfg.moe, MoEConfig)
    assert cfg.moe == dataclasses.replace(base.moe, num_experts=8)
    assert cfg.n_layers == 4 and cfg.d_model == base.d_model
    # where the program has none, the file's sizes build one
    doc = {**MOE_DOC, "arch": "yi-6b",
           "model": {"moe": {"num_experts": 8, "top_k": 2, "d_expert": 64}}}
    assert Model(doc).program_config().moe == MoEConfig(8, 2, 64)
    # the harness's readers still see plain dicts
    assert Model(MOE_DOC).c["moe"] == {"num_experts": 8}


def test_config_smoke_overlay_keeps_the_sub_keys_it_does_not_state():
    model = Model(MOE_DOC, smoke=True)
    assert model.c["moe"] == {"num_experts": 8, "d_expert": 64}
    cfg = model.program_config()
    assert (cfg.moe.num_experts, cfg.moe.d_expert, cfg.moe.top_k) == (8, 64, 8)
    assert cfg.n_layers == 2 and cfg.dtype == "float32"


@pytest.mark.parametrize("model,where", [
    ({"n_layers": 4, "n_expert": 8}, "ModelConfig"),
    ({"moe": {"num_experts": 8, "n_shared": 2}}, "MoEConfig")])
def test_config_unknown_key_is_refused(model, where):
    with pytest.raises(KeyError, match=where):
        Model({**MOE_DOC, "model": model}).program_config()


def test_config_unknown_block_stack_is_refused():
    """A stack with no module under ``bench/blocks/`` is refused, never
    run as another."""
    with pytest.raises(ValueError, match="mla_experts"):
        Model({**MOE_DOC, "blocks": "mla_experts"})


def test_weights_draw_a_bias_leaf():
    import jax
    import jax.numpy as jnp

    from bench import weights

    sd = jax.ShapeDtypeStruct
    p = weights.make_params({"final_norm": sd((64,), jnp.float32),
                             "router": {"w": sd((32, 64), jnp.float32),
                                        "e_score_correction_bias":
                                            sd((4096,), jnp.float32)}}, 3)
    bias = np.asarray(p["router"]["e_score_correction_bias"])
    assert abs(bias.mean()) < 0.01 and bias.std() == pytest.approx(0.1,
                                                                   rel=0.05)
    assert np.asarray(p["final_norm"]).mean() == pytest.approx(1.0, abs=0.05)
    assert np.asarray(p["router"]["w"]).std() == pytest.approx(
        32 ** -0.5, rel=0.1)


def _yi6b_smoke_params(seed):
    import jax

    from bench import weights
    from repro.models import model as M

    cfg = _yi6b(smoke=True).program_config()
    shapes = jax.eval_shape(lambda k: M.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    return weights.make_params(shapes, seed)


def test_weights_yi6b_smoke_tree_is_pinned():
    """yi-6b's arrays, leaf by leaf, as the weights were drawn before
    bias leaves had a rule of their own."""
    import hashlib

    import jax

    pins = _json(PINS)
    flat = jax.tree_util.tree_flatten_with_path(
        _yi6b_smoke_params(pins["seed"]))[0]
    got = {jax.tree_util.keystr(path): hashlib.sha256(
        np.asarray(v).tobytes()).hexdigest() for path, v in flat}
    assert got == pins["weights_sha256"]


def test_reference_gqa_swiglu_is_pinned():
    """The reference's guided budget-4 sample at yi-6b's smoke sizes, in
    float32 and in the fp8 control, equals exactly what the reference
    gave before its block stack moved into ``bench/blocks/``
    (``testdata/yi6b_smoke_reference.npz``); jitted as the check jits it."""
    import jax

    from bench import reference

    pins = _json(PINS)
    model = _yi6b(smoke=True)
    c = model.c
    params = _yi6b_smoke_params(pins["seed"])
    rng = np.random.default_rng(pins["seed"])
    x0 = jax.numpy.asarray(rng.standard_normal((2, 8, c["latent_dim"]),
                                               np.float32))
    tok = jax.numpy.asarray(rng.integers(0, c["vocab"], (2, 8)).astype(
        np.int32))
    want = np.load(os.path.join(TESTDATA, "yi6b_smoke_reference.npz"))
    for mode in ("f32", "fp8"):
        step = jax.jit(lambda p, t, x, tk, mode=mode: reference.guided(
            p, model.stack, c, t, x, tk, 1.5, mode))
        got = reference.flow_sample(lambda t, x: step(params, t, x, tok),
                                    (4, 8, 16), 4, x0)
        np.testing.assert_array_equal(np.asarray(got), want[mode])


# -- traffic -----------------------------------------------------------------------


CFG = {"vocab": 1000, "latent_dim": 8}


def _flow_mix():
    return _json("bench/traffic/flow_mixed.json")


def test_traffic_same_seed_same_schedule():
    mix = _flow_mix()
    a = loadgen.schedule(mix, 7, 20.0, "window", CFG)
    b = loadgen.schedule(mix, 7, 20.0, "window", CFG)
    assert [x.t for x in a] == [x.t for x in b]
    assert [x.spec["budget"] for x in a] == [x.spec["budget"] for x in b]
    assert all(np.array_equal(x.spec["x0"], y.spec["x0"])
               and np.array_equal(x.spec["tokens"], y.spec["tokens"])
               for x, y in zip(a, b))


def test_traffic_seeds_share_the_work_not_the_order():
    mix = _flow_mix()
    mix["arrival"].pop("order_seed", None)
    a = loadgen.schedule(mix, 1, 40.0, "window", CFG)
    b = loadgen.schedule(mix, 2 ** 31 + 11, 40.0, "window", CFG)
    ba = [x.spec["budget"] for x in a]
    bb = [x.spec["budget"] for x in b]
    assert len(a) == len(b) and sorted(ba) == sorted(bb) and ba != bb
    assert [x.t for x in a] != [x.t for x in b]
    w = loadgen.schedule(mix, 1, 40.0, "warmup", CFG)
    assert not np.array_equal(w[0].spec["x0"], a[0].spec["x0"])


def test_traffic_order_seed_replays_one_order_for_every_seed():
    mix = _flow_mix()
    mix["arrival"]["order_seed"] = 12
    a = loadgen.schedule(mix, 1, 40.0, "window", CFG)
    b = loadgen.schedule(mix, 2 ** 31 + 11, 40.0, "window", CFG)
    assert [x.t for x in a] == [x.t for x in b]
    assert [x.spec["budget"] for x in a] == [x.spec["budget"] for x in b]
    assert not any(np.array_equal(x.spec["x0"], y.spec["x0"])
                   for x, y in zip(a, b))


def test_traffic_flow_arrivals_follow_the_mix():
    mix = _flow_mix()
    rate = mix["arrival"]["rate_per_s"]
    arr = loadgen.schedule(mix, 3, 400.0, "window", CFG)
    assert len(arr) == int(np.ceil(rate * 400.0))
    t = np.array([a.t for a in arr])
    assert t[0] == 0.0 and np.all(np.diff(t) > 0)
    assert np.mean(np.diff(t)) == pytest.approx(1 / rate, rel=0.05)
    # stratified exponential gaps: their median is ln 2 / rate
    assert np.median(np.diff(t)) == pytest.approx(np.log(2) / rate,
                                                  rel=0.05)


def test_traffic_flow_budget_shares_exact():
    mix = _flow_mix()
    arr = loadgen.schedule(mix, 5, 40.0, "window",
                           {"vocab": 64000, "latent_dim": 64})
    budgets = [a.spec["budget"] for a in arr]
    n = len(budgets)
    for b, w in zip(mix["requests"]["budgets"],
                    mix["requests"]["budget_weights"]):
        assert abs(budgets.count(b) - n * w / 4) <= 1
    a = arr[0].spec
    assert a["x0"].shape == (64, 64) and a["tokens"].shape == (64,)


@pytest.mark.parametrize("part,key,name", [
    ("arrival", "process", "bursts"), ("requests", "kind", "chat")])
def test_traffic_unknown_generator_is_refused(part, key, name):
    """A mix naming an arrival process or a request kind that has no
    module under ``bench/generators/`` is refused, never run as
    another."""
    mix = _flow_mix()
    mix[part][key] = name
    with pytest.raises(ValueError, match=name):
        loadgen.schedule(mix, 1, 10.0, "window", CFG)


# -- the contract of BENCHMARK.json ---------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_json_follows_the_contract():
    b = _json("BENCHMARK.json")
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["bench"] and 1 <= b["run_seconds"] <= 51
    # the whole check of 24 cells fits its 12-hour budget
    cells = 24
    runs = 2 + 14 * cells
    assert runs * (b["run_seconds"] + 60) + cells * 180 + 1200 <= 43200
    configs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        doc = _json(c["file"])
        assert set(c["reduced"]) == set(doc["reduced"])
        assert len(c["why"]) <= 200
    cells_by = {}
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert os.path.exists(os.path.join(
            ROOT, "bench", "traffic", w["traffic"] + ".json"))
        cells_by[w["name"]] = w
    names = set()
    for group, keys in (("end_to_end", {"name", "unit", "better", "bound",
                                        "source", "workloads"}),
                        ("per_layer", {"name", "unit", "better", "source",
                                       "layer", "moves", "workloads"})):
        for m in b[group]:
            assert set(m) <= keys and NAME.match(m["name"])
            assert UNIT.match(m["unit"]) and m["better"] in ("lower",
                                                            "higher")
            assert m["name"] not in names
            names.add(m["name"])
            assert os.path.exists(os.path.join(ROOT, "bench", "metrics",
                                               m["name"] + ".py"))
            for cell in m.get("workloads", []):
                assert cell in cells_by
            if group == "end_to_end":
                assert m["source"] in ("host_clock", "device_trace")
                assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        for cell in m["workloads"]:
            assert cell in e2e[m["moves"]].get("workloads", [cell])
    for cell in cells_by:
        reported = [m for m in b["end_to_end"]
                    if cell in m.get("workloads", [cell])]
        assert len(reported) >= 2
        assert any(cell in m["workloads"] for m in b["per_layer"])


# -- runs at smoke size ------------------------------------------------------------


def _run(*argv):
    from bench import run

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run.main(list(argv))
    assert rc == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_smoke_run_names_the_cpu(cell):
    out = _run("--workload", cell, "--smoke", "--seed", "2147483659")
    assert list(out)[-1] == "checks"
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["metrics"] == {}
    assert out["device"]["platform"] == "cpu"
    for ch in out["checks"].values():
        assert ch["value"] is not None and ch["value"] <= ch["limit"]


def test_without_a_chip_no_result():
    from bench import run

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run.main(["--workload", "yi6b.flow.mixed", "--seconds", "1"])
    assert rc != 0 and buf.getvalue() == ""


def test_broken_timed_path_reads_incorrect(monkeypatch):
    """An answer altered where it is produced: every sample the sampler
    hands the gateway is shifted, and the run reads incorrect."""
    from repro.serving.engine import AnytimeFlowSampler

    extend, sample = AnytimeFlowSampler.carry_extend, \
        AnytimeFlowSampler.sample_from
    sample_all = AnytimeFlowSampler.sample_all_from

    def carry_extend(self, batch, carry, stop):
        carry, exits = extend(self, batch, carry, stop)
        return carry, {k: v + 0.5 for k, v in exits.items()}

    monkeypatch.setattr(AnytimeFlowSampler, "carry_extend", carry_extend)
    monkeypatch.setattr(AnytimeFlowSampler, "sample_from",
                        lambda self, *a: sample(self, *a) + 0.5)
    monkeypatch.setattr(AnytimeFlowSampler, "sample_all_from",
                        lambda self, *a: {k: v + 0.5 for k, v in
                                          sample_all(self, *a).items()})
    out = _run("--workload", "yi6b.flow.mixed", "--smoke", "--seed", "5")
    assert out["correct"] is False
    assert any(ch["value"] > ch["limit"] for ch in out["checks"].values())


def test_control_reads_above_the_program():
    """The control (the reference at fp8) runs beside the program on the
    same sampled requests; at smoke size, where the program computes in
    float32, it must read far above the program."""
    import time

    from bench import run

    ctx = run.prepare("yi6b.flow.mixed", smoke=True)
    ctx.update(seed=9, seconds=3.0, trace=False, t_start=time.perf_counter(),
               control=True)
    checks = run.execute(ctx)["checks"]
    prog = checks["latents_rel_l2.b16"]["value"]
    ctrl = checks["control_fp8_rel_l2.b16"]["value"]
    assert ctrl > 0 and ctrl > 10 * prog


# -- metric readers --------------------------------------------------------------


def _reader(name):
    from bench import run

    return run.reader(name)


def _records():
    """Eight requests sent 1 s apart, admitted 0.5 s after sending, done
    2 s after admission; the last two straddle or follow the window's
    close at 10 s, and one failed."""
    recs = [{"t_sched": float(t), "t_sent": float(t), "t_admit": t + 0.5,
             "t_done": t + 2.5, "ok": True, "served": b, "spec": {"budget": b}}
            for t, b in zip(range(0, 16, 2), [4, 8, 16, 8] * 2)]
    recs[1]["ok"] = False
    return recs


def _fake_run(cell, traced):
    from bench.model import Model

    b = _json("BENCHMARK.json")
    w = next(x for x in b["workloads"] if x["name"] == cell)
    entry = next(c for c in b["configs"] if c["name"] == w["config"])
    mix = _json(f"bench/traffic/{w['traffic']}.json")
    run = {"mix": mix, "model": Model(_json(entry["file"])),
           "peaks": _json("bench/peaks.json")["kinds"]["TPU v5 lite"],
           "t0": 0.0, "t1": 10.0, "window_s": 10.0, "setup_s": 30.0,
           "records": _records(),
           "counters": {"wait_ms.count": 4, "wait_ms.sum": 100.0,
                        "forwards": 40, "join_forwards": 8,
                        "slot_steps_active": 30, "slot_steps_total": 64}}
    run["trace"] = ({"busy_s": 9.0, "profile_s": 10.0,
                     "programs": {"flow": 6.0}, "kernels": {},
                     "spans": [("continuous.leg.0-4", 1.0, 1.1)]}
                    if traced else None)
    return run


@pytest.mark.parametrize("cell", CELLS)
def test_readers_read_their_cell_and_stay_in_range(cell):
    b = _json("BENCHMARK.json")
    for group, traced in (("end_to_end", False), ("per_layer", True)):
        run = _fake_run(cell, traced)
        for m in b[group]:
            if cell not in m.get("workloads", [cell]):
                continue
            v = _reader(m["name"])(run)
            assert v is not None and v > 0, m["name"]
            if m["unit"] == "%":
                assert v <= 100, m["name"]
    # without a trace, a reader of the device says nothing
    untraced = _fake_run(cell, False)
    for m in b["per_layer"]:
        if cell in m["workloads"] and m["source"] == "device_trace":
            assert _reader(m["name"])(untraced) is None, m["name"]


@pytest.mark.parametrize("name", ["flow.mfu", "flow.backbone_roofline",
                                  "flow.backbone_bound"])
def test_work_readers_yi6b_pinned(name):
    """The readers built on the work counts read, on the synthetic run,
    exactly what they read when the counts were yi-6b's own formulas."""
    run = _fake_run("yi6b.flow.mixed", True)
    assert _reader(name)(run) == _json(PINS)["readers"][name]


def test_flow_row_steps_count_requests_inside_the_window():
    from bench.readers import flow_row_steps

    run = _fake_run("yi6b.flow.mixed", True)
    # whole: t=0 (4), t=4 (16), t=6 (8); failed: t=2; t=8 is admitted at
    # 8.5 and done at 10.5, three quarters inside (budget 4: 3 steps);
    # t=10 and later were admitted after the close
    assert flow_row_steps(run) == pytest.approx(4 + 16 + 8 + 3)


def test_backbone_roofline_counts_the_requests_not_the_padding():
    """The share reads the rows the requests needed: the same requests
    over the same device time read the same share whatever the dispatched
    batches held, and the least time of the requests' steps, at peak,
    over the device time."""
    peaks = _json("bench/peaks.json")["kinds"]["TPU v5 lite"]
    read = _reader("flow.backbone_roofline")
    run = _fake_run("yi6b.flow.mixed", True)
    v = read(run)
    run["trace"]["spans"] = [("continuous.leg.0-16", 1.0, 1.1),
                             ("gateway.dispatch.b4/k16", 2.0, 2.1)]
    assert read(run) == v
    least = 31 * work.flow_request_flops(_yi6b(), 1, 64, True) / peaks[
        "bf16_flop_per_s"]
    assert v == pytest.approx(100 * least / 6.0)
