"""CPU tests of what the flow plane warms before its window, and of the
kernels a configuration's block module names for the trace reduction.

``_warm_programs`` is driven by a stub sampler that records each call the
gateway could also make (carry start, leg, flush, merged flush) and each
change of a carry (a narrowed leg, a join's scatter), so the list of
programs a server gets warmed is pinned call by call."""
import dataclasses
import sys
import time
import types

import numpy as np
import pytest

from bench import harness, trace
from bench.blocks import gqa_swiglu
from bench.model import Model, stack_module
from bench.planes import flow


@dataclasses.dataclass
class _Carry:
    x0: object
    U: object
    x: object
    step: int
    rows: object = None
    log: list = None

    def _replace(self, **kw):
        if "rows" in kw:
            self.log.append(("narrow", len(kw["rows"])))
        else:
            self.log.append(("scatter",) + tuple(sorted(kw)))
        return dataclasses.replace(self, **kw)


class _Sampler:
    """The sampler protocol the plane warms, recording each call by the
    rows it runs and the steps it spans."""

    def __init__(self, budgets):
        self.budgets, self.log = tuple(budgets), []

    def carry_start(self, cond, x):
        import jax.numpy as jnp

        self.log.append(("start", x.shape[0]))
        return _Carry(x, jnp.zeros((max(self.budgets),) + x.shape), x, 0,
                      log=self.log)

    def carry_extend(self, cond, carry, stop):
        width = carry.x.shape[0] if carry.rows is None else len(carry.rows)
        self.log.append(("extend", carry.step, stop, width))
        out = dataclasses.replace(carry, step=stop, rows=None)
        return out, {stop: carry.x}

    def sample_from(self, cond, x, m):
        self.log.append(("flush", x.shape[0], m))
        return x

    def sample_all_from(self, cond, x):
        self.log.append(("merged", x.shape[0]))
        return {m: x for m in self.budgets}


def _server(budgets, slots, policy="auto"):
    return {"budgets": budgets, "cfg_scale": 1.5, "scheduler": "fm_ot",
            "max_slots": slots, "max_wait_ms": 10.0,
            "mixed_budget_policy": policy}


def _warm_log(srv):
    sampler = _Sampler(srv["budgets"])
    flow._warm_programs(sampler, srv, 4, 2)
    return sampler.log


def _multi_budget_calls(budgets, slots, policy):
    """The calls a multi-budget server was warmed with before one-budget
    servers had a path of their own, in their order."""
    calls = []
    for k in harness.pow2_upto(slots):
        for b in budgets[:-1]:
            calls += [("start", k), ("extend", 0, b, k)]
        calls += [("flush", k, m) for m in budgets]
        if policy != "never":
            calls.append(("merged", k))
    calls.append(("start", slots))
    step = 0
    for b in budgets:
        calls.append(("extend", step, b, slots))
        step = b
    return calls + [("scatter", "U", "x", "x0")] * slots


@pytest.mark.parametrize("budgets,slots,policy", [
    ([4, 8, 16], 16, "auto"),           # flow_mixed
    ([4, 8, 16], 2, "auto"),            # flow_mixed at smoke size
    ([2, 4], 8, "never"),
    ([4, 8], 3, "always")])
def test_multi_budget_server_warms_what_it_did(budgets, slots, policy):
    assert _warm_log(_server(budgets, slots, policy)) == _multi_budget_calls(
        budgets, slots, policy)


@pytest.mark.parametrize("slots,policy", [(8, "auto"), (4, "never"),
                                          (5, "always")])
def test_one_budget_server_warms_flushes_and_every_leg_width(slots, policy):
    """One budget: no exit boundary to join at, nothing to merge. Flush
    batches at every bucket, then the leg 0..4 at every width the gateway
    can narrow it to, the full width unnarrowed; no join prefix, no join
    scatter, no merged flush."""
    log = _warm_log(_server([4], slots, policy))
    ladder = harness.pow2_upto(slots)
    legs = []
    for w in ladder:
        legs += ([("extend", 0, 4, slots)] if w == slots
                 else [("narrow", w), ("extend", 0, 4, w)])
    assert log == ([("flush", k, 4) for k in ladder] + [("start", slots)]
                   + legs)
    assert not [c for c in log if c[0] in ("scatter", "merged")]
    assert [c for c in log if c[0] == "start"] == [("start", slots)]


@pytest.mark.parametrize("srv,what", [
    ({"budgets": []}, "budgets"),
    ({"max_slots": 0}, "max_slots"),
    ({"max_leg": 2}, "max_leg")])
def test_a_server_the_plane_cannot_warm_is_refused(srv, what):
    with pytest.raises(ValueError, match=what):
        _warm_log({**_server([4, 8], 4), **srv})


def test_one_budget_unguided_smoke_run_compiles_nothing_in_its_window():
    """The interactive cell's server (one budget, ``cfg_scale`` 0) at
    smoke size through the whole plane: the window's requests all settle,
    the check reads correct, and the window compiles nothing although the
    smoke mix sends no warm-up traffic."""
    from bench import run

    ctx = run.prepare("yi6b.flow.interactive", smoke=True)
    srv = ctx["mix"]["server"]
    assert srv["budgets"] == [4] and srv["cfg_scale"] == 0.0
    assert ctx["mix"].get("warmup_seconds", 0.0) == 0.0
    ctx.update(seed=2 ** 31 + 17, seconds=3.0, trace=False,
               t_start=time.perf_counter())
    out = flow.run(ctx)
    assert out["compiles_in_window"] == 0
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["checks"]) == {"latents_rel_l2.b4"}
    ch = out["checks"]["latents_rel_l2.b4"]
    assert ch["value"] is not None and ch["value"] <= ch["limit"]


# -- kernels named by the block module ----------------------------------------


def _probe_stack(monkeypatch, name, **attrs):
    """A throwaway block module ``bench.blocks.<name>``: yi-6b's stack
    with ``attrs`` set (or, with a value of None, taken away)."""
    mod = types.ModuleType(f"bench.blocks.{name}")
    mod.__dict__.update({k: v for k, v in vars(gqa_swiglu).items()
                         if not k.startswith("__")})
    for k, v in attrs.items():
        if v is None:
            delattr(mod, k)
        else:
            setattr(mod, k, v)
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    return mod


def test_yi6b_block_stack_names_no_kernel():
    """yi-6b's reduction credits no kernel, as before kernels came from
    the block module."""
    assert gqa_swiglu.KERNELS == {}


def test_block_module_without_kernels_is_refused(monkeypatch):
    _probe_stack(monkeypatch, "no_kernels_probe", KERNELS=None)
    with pytest.raises(ValueError, match="no_kernels_probe.*KERNELS"):
        stack_module("no_kernels_probe")


def _flow_trace(kernel_op: str):
    """A device trace of one leg and one flush of the flow programs, each
    running a matmul fusion and then the named custom call."""
    from bench.tests.test_bench import Data, Ev, Line, Plane

    ms = 1_000_000
    mods = [Ev("jit__extend(7)", 0, 10 * ms), Ev("jit__sample(3)", 20 * ms,
                                                  10 * ms)]
    ops = [Ev("%fusion.4 = bf16[8] fusion(...), kind=kOutput", 0, 4 * ms),
           Ev(f"%{kernel_op}.2 = bf16[8] custom-call(...)", 4 * ms, 6 * ms),
           Ev("%fusion.4 = bf16[8] fusion(...), kind=kOutput", 20 * ms,
              7 * ms),
           Ev(f"%{kernel_op}.2 = bf16[8] custom-call(...)", 27 * ms, 3 * ms)]
    return Data([Plane("/device:TPU:0", [Line("XLA Modules", mods),
                                         Line("XLA Ops", ops)]),
                 Plane("Task Environment", [], (("profile_start_time", 0),
                                                ("profile_stop_time",
                                                 40 * ms)))])


def test_kernels_of_the_block_module_reach_the_readers(monkeypatch):
    """A block module that names a kernel: the flow plane hands its
    ``KERNELS`` to the trace reduction, and the kernel's device time, split
    by program family, is what the readers get under
    ``run["trace"]["kernels"]``."""
    from bench import run

    kernels = {"grouped_matmul": ("gmm_probe",)}
    _probe_stack(monkeypatch, "kernel_probe", KERNELS=kernels)
    seen = []

    def reduce(self, programs, kern):
        seen.append(kern)
        return trace.reduce(None, programs, kern,
                            data=_flow_trace("gmm_probe"))

    monkeypatch.setattr(harness.TracedWindow, "start", lambda self: None)
    monkeypatch.setattr(harness.TracedWindow, "stop", lambda self: None)
    monkeypatch.setattr(harness.TracedWindow, "reduce", reduce)
    ctx = run.prepare("yi6b.flow.interactive", smoke=True)
    ctx["model"] = Model({**ctx["model"].doc, "blocks": "kernel_probe"},
                         smoke=True)
    ctx.update(seed=23, seconds=1.0, trace=True, t_start=time.perf_counter())
    out = flow.run(ctx)
    assert seen == [kernels]
    assert out["trace"]["kernels"] == {
        "grouped_matmul": {"flow": pytest.approx(0.009)}}
    assert out["device"]["busy_s"] == pytest.approx(0.020)
    assert np.isfinite(out["checks"]["latents_rel_l2.b4"]["value"])
