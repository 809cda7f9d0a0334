"""Flow plane: anytime BNS sampling through ``ContinuousGateway``.

Set-up makes the weights from the seed, builds the ``AnytimeFlowSampler``
over the undistilled anytime solver (``core.anytime.init_anytime``: a
budget-m request costs m guided forwards whatever the coefficients are,
and distilling would add a minute to every run) and one
``ContinuousGateway``. It warms every program the gateway can dispatch
for the server the mix names (``_warm_programs``: flush batches and
trajectory legs, and with more than one budget join prefixes, merged
flushes and the small eager scatters of a join), then sends warm-up
traffic from its own seed stream through the same gateway. The trace is
reduced with the kernels the configuration's block module names
(``KERNELS``).

The window: open-loop arrivals from ``loadgen`` (entry ``submit``), each
timed from its scheduled send until its latents are on the host (the
future's callback). After the window, traffic keeps coming from the
``after`` stream until every window request has settled; then the
gateway stops and its state is freed.

``correct``: a sample of the window's completed requests drawn from the
seed, ``check.per_budget`` of every served budget, is compared with the
plain reference (``bench.reference`` over the block stack the
configuration names, ``Model.stack``; float32): the widest relative L2
error of each budget's latents against that budget's limit.
"""
from __future__ import annotations

import numpy as np

from bench import harness, loadgen, reference
from bench.harness import log, now


# the server settings the plane builds its sampler and gateway from
SERVER_KEYS = {"budgets", "cfg_scale", "scheduler", "max_slots",
               "max_wait_ms", "mixed_budget_policy"}


def _warm_programs(sampler, srv, S: int, L: int):
    """Run once every program the gateway can dispatch for this server
    and traffic (``S`` positions of width ``L``), at every padded batch
    size it can choose: flush batches at every bucket and the trajectory
    legs from step 0. With more than one budget also the join prefixes to
    each exit boundary below the top, the merged flush (unless the policy
    is ``never``) and the small eager scatters of a join; legs run at full
    width here, and the gateway's first trajectory warms their narrow
    widths during the warm-up traffic. With one budget there is no
    boundary to join at and nothing to merge, and the one leg is warmed
    here at every width of the ladder, so the window compiles nothing
    whatever the warm-up traffic. A server with no budget, no slot, or a
    setting the plane does not pass on is refused."""
    import jax
    import jax.numpy as jnp

    unknown = sorted(set(srv) - SERVER_KEYS)
    if unknown or not srv["budgets"] or srv["max_slots"] < 1:
        raise ValueError(f"flow: cannot warm the server {srv}: budgets "
                         f"and max_slots >= 1 needed, unknown {unknown}")
    budgets = sorted(srv["budgets"])
    joins = budgets[:-1]                        # exit boundaries to join at
    slots = srv["max_slots"]
    prefix = None
    for k in harness.pow2_upto(slots):
        cond = {"tokens": jnp.zeros((k, S), jnp.int32)}
        x = jnp.zeros((k, S, L), jnp.float32)
        outs = []
        for b in joins:                         # join prefixes 0..b
            c = sampler.carry_start(cond, x)
            prefix, _ = sampler.carry_extend(cond, c, b)
            for i in range(k):                  # per-slot carry columns
                prefix.x0[i], prefix.U[:, i], prefix.x[i]
            outs.append(prefix.x)
        for m in budgets:                       # flush batches
            outs.append(sampler.sample_from(cond, x, m))
        if joins and srv["mixed_budget_policy"] != "never":
            sampler.sample_all_from(cond, x)
        jax.block_until_ready(outs)
    cond = {"tokens": jnp.zeros((slots, S), jnp.int32)}
    carry = sampler.carry_start(cond, jnp.zeros((slots, S, L), jnp.float32))
    if not joins:                               # the one leg, every width
        for w in harness.pow2_upto(slots):
            c = carry if w == slots else carry._replace(
                rows=jnp.asarray(np.arange(w, dtype=np.int32)))
            jax.block_until_ready(sampler.carry_extend(cond, c, budgets[0]))
        return
    for b in budgets:                           # trajectory legs
        carry, _ = sampler.carry_extend(cond, carry, b)
    for j in range(1, slots + 1):               # join scatters of j rows
        idx = jnp.asarray(list(range(j)))
        carry._replace(
            x0=carry.x0.at[idx].set(jnp.stack([prefix.x0[0]] * j)),
            U=carry.U.at[:, idx].set(jnp.stack([prefix.U[:, 0]] * j,
                                               axis=1)),
            x=carry.x.at[idx].set(jnp.stack([prefix.x[0]] * j)))
    jax.block_until_ready(carry)


def run(ctx) -> dict:
    import jax

    from repro.core.anytime import init_anytime
    from repro.core.schedulers import get_scheduler
    from repro.models import model as M
    from repro.serving import AnytimeFlowSampler
    from repro.serving.continuous import ContinuousGateway
    from repro.serving.gateway import Request

    from bench import weights

    model, mix, seed = ctx["model"], ctx["mix"], ctx["seed"]
    srv, c = mix["server"], model.c
    cfg = model.program_config()
    compiles = harness.CompileCounter()

    shapes = jax.eval_shape(lambda k: M.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    params = jax.block_until_ready(weights.make_params(shapes, seed))
    budgets = tuple(sorted(srv["budgets"]))
    sampler = AnytimeFlowSampler(
        params=params, cfg=cfg, sched=get_scheduler(srv["scheduler"]),
        anytime=init_anytime(None, budgets), budgets=budgets,
        cfg_scale=srv["cfg_scale"])
    gw = ContinuousGateway(sampler, max_slots=srv["max_slots"],
                           max_wait_ms=srv["max_wait_ms"],
                           mixed_budget_policy=srv["mixed_budget_policy"])
    _warm_programs(sampler, srv, mix["requests"]["positions"], c["latent_dim"])
    log(f"flow: programs warm at {now() - ctx['t_start']:.1f} s "
        f"({compiles.n} compilations)")

    def submit(a, rec):
        s = a.spec
        fut = gw.submit(Request(tokens=s["tokens"], budget=s["budget"],
                                x0=s["x0"]))

        def done(f, rec=rec):
            t = now()
            if f.exception() is None:       # what the backbone readers need
                meta = f.result().meta
                rec["served"] = meta["served_budget"]
                rec["t_admit"] = rec["t_sent"] + meta["wait_ms"] / 1e3
            rec["t_done"] = t
            rec["ok"] = f.exception() is None

        fut.add_done_callback(done)
        return {"future": fut, "phase": a.spec.get("phase")}

    gw.start()
    warm_s = mix.get("warmup_seconds", 0.0)
    if warm_s:
        warm: list = []
        harness.Sender(loadgen.schedule(mix, seed, warm_s, "warmup", c),
                       now(), submit, warm).run()
        harness.wait_all(warm, now() + 120,
                         lambda r: r.get("t_done") is not None)
    log(f"flow: warm-up traffic done at {now() - ctx['t_start']:.1f} s")

    meas = harness.measure_window(
        ctx, gw, submit, compiles, settle_s=60.0,
        settled=lambda r: r.get("t_done") is not None or r["ok"] is False)
    window = meas["records"]
    served = {i: np.asarray(r["future"].result().latents)
              for i, r in enumerate(window) if r.get("ok")}
    del gw, sampler
    return harness.result(ctx, meas, PROGRAMS, model.stack.KERNELS,
                          lambda: _check(ctx, params, window, served))


# programs the trace readers look for (stable jit names); the kernels are
# the ones the configuration's block module names (``KERNELS``)
PROGRAMS = {"flow": ("jit__extend", "jit__sample")}


def _sample(ctx, window, results) -> list[int]:
    """Indices of window requests to compare: ``check.per_budget`` of each
    served budget (the top one is the longest request), drawn from the
    seed's check stream."""
    rng = loadgen.rng_for(ctx["seed"], "check")
    per = ctx["mix"]["check"]["per_budget"]
    chosen = []
    for m in sorted(ctx["mix"]["server"]["budgets"]):
        idx = [i for i in results if window[i]["spec"]["budget"] == m]
        chosen += sorted(rng.permutation(idx)[:per].tolist())
    return chosen


def _check(ctx, params, window, results):
    """Relative L2 error of each sampled served latent against the
    float32 reference over the configuration's block stack; the widest
    of each served budget is held to that budget's limit (an early exit
    averages the velocities it has seen, the top budget chains 16
    evaluations, so rounding grows about five times from budget 8 to 16;
    one limit would let the lower budgets drift unseen). With
    ``ctx['control']`` (``bench/control.py``, never a benchmark run) the
    same samples are also computed by the control (the reference at fp8)
    and its errors from the float32 reference are read beside."""
    import jax
    import jax.numpy as jnp

    srv, model = ctx["mix"]["server"], ctx["model"]
    chosen = _sample(ctx, window, results)
    budgets = sorted(srv["budgets"])
    modes = ("f32", "fp8") if ctx.get("control") else ("f32",)
    steps = {m: jax.jit(lambda p, t, x, tok, m=m: reference.guided(
        p, model.stack, model.c, t, x, tok, srv["cfg_scale"], m))
        for m in modes}
    errs: dict = {}                     # (reading, budget) -> [errors]
    for b in budgets:
        group = [i for i in chosen if window[i]["spec"]["budget"] == b]
        if not group:
            continue
        x0 = jnp.asarray(np.stack([window[i]["spec"]["x0"] for i in group]))
        tok = jnp.asarray(np.stack([window[i]["spec"]["tokens"]
                                    for i in group]))
        out = {m: np.asarray(reference.flow_sample(
            lambda t, x, m=m: steps[m](params, t, x, tok), budgets, b, x0))
            for m in modes}
        ref = out["f32"]
        for j, i in enumerate(group):
            for m in modes:
                g = results[i] if m == "f32" else out[m][j]
                errs.setdefault((m, b), []).append(float(
                    np.linalg.norm(g - ref[j]) / np.linalg.norm(ref[j])))
    for (m, b), e in sorted(errs.items()):
        log(f"flow: {'program' if m == 'f32' else m} budget {b}: relative "
            f"L2 max {max(e):.4g} mean {np.mean(e):.4g} over {len(e)}")
    limits = ctx["mix"]["check"]["latents_rel_l2"]
    checks = {}
    for m in modes:
        name = "latents_rel_l2" if m == "f32" else f"control_{m}_rel_l2"
        for b in budgets:
            e = errs.get((m, b))
            checks[f"{name}.b{b}"] = {"value": max(e) if e else None,
                                      "limit": limits[str(b)]}
    return checks
