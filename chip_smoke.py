#!/usr/bin/env python3
"""Smoke test of the serving path on a TPU: yi-6b at its published widths.

    python3 chip_smoke.py                # one chip: flow + paged decode
    python3 chip_smoke.py --four-chips   # a four-chip host: sharded flow
    python3 chip_smoke.py --smoke        # CPU rehearsal at smoke sizes

Flow phase (the product path): random yi-6b weights from a seed; serve.py's
own distillation makes an anytime BNS artifact (budgets 2,4,8) under
``smoke_out/``; a fresh ``SolverZoo`` reloads it from disk
with no retraining; ``AnytimeFlowSampler`` -> ``ContinuousGateway`` serves
mixed-budget requests with the XLA update and with the Pallas ``ns_update``
kernel (``--kernel-update``). Checks: every future resolves, every sample
is finite, a lone request through the flush ``Gateway`` is bit-identical
to ``sample_from`` on the same noise (the same program on the same
inputs), each served sample agrees with the direct sampler within
``BF16_REL_TOL``, and the kernel update agrees with the XLA weighted sum
within ``UPDATE_TOL``.

Decode phase: ``DecodeGateway`` over a paged KV cache (page size 16)
through the Pallas paged-attention kernel (``--paged-kernel``), prompts of
mixed lengths, against the dense-gather path of the same engine: greedy
tokens are equal up to the first step where the two paths pick different
tokens, and there the two tokens' logits in a full forward of the same
prefix tie within ``LOGIT_TIE_ULPS`` bf16 steps. The kernel itself must
match its dense-gather oracle at yi-6b decode widths (``ATTN_TOL``).

``--four-chips`` runs only the flow gateway on a (1, 4) mesh over the
host's four devices, with params placed by ``sharded.shard_params``, and
the same requests on one device as the comparison (``BF16_REL_TOL``).

On a TPU the lowered kernel programs must contain ``tpu_custom_call``.
Without a TPU the script exits non-zero and prints no result (``--smoke``
rehearses on the CPU, kernels in interpret mode, and its last line names
the CPU). Any failed phase exits non-zero. The last line of stdout is
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

OUT_DIR = os.path.join(ROOT, "smoke_out")
BUDGETS = (2, 4, 8)
# Relative L2 error allowed between two computations of one sample that
# differ only in bf16 rounding: batch mates (a v5e runs a batch of 8 and a
# batch of 1 through different tilings; measured up to 2.5e-2 at budget
# 8), the NS update's summation order, or partial sums reduced across
# chips. Twice the measured batch-shape spread.
BF16_REL_TOL = 5e-2
# the NS update is elementwise f32 arithmetic on both sides (relative L2)
UPDATE_TOL = 1e-5
# paged attention kernel vs its dense-gather oracle (relative L2)
ATTN_TOL = 2e-2
# logits are bf16: greedy decode breaks ties by token id, so two attention
# paths may part where the top two logits are this many bf16 steps apart
LOGIT_TIE_ULPS = 2


def log(msg: str) -> None:
    print(msg, flush=True)


def _timed(fn):
    """(result, seconds), the clock read after the result is on the host."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def _serve_args(smoke: bool, extra=()):
    from repro.launch.serve import build_parser

    return build_parser().parse_args(
        ["--arch", "yi-6b", "--smoke" if smoke else "--full",
         "--budgets", ",".join(map(str, BUDGETS)),
         "--batch", "2", "--seq", "16",
         "--bns-iters", "8" if smoke else "32",
         "--gateway", "--continuous", "--max-slots", "8", "--max-batch", "8",
         "--requests", "12", *extra])


def _check_rel(name, got, want, tol) -> float:
    """||got - want|| / ||want||, raising above ``tol``."""
    import numpy as np

    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    if not rel <= tol:
        raise AssertionError(f"{name}: relative L2 error {rel:.3e} exceeds "
                             f"{tol:g}")
    return rel


def _has_kernel(jitted, *args) -> bool:
    return "tpu_custom_call" in jitted.lower(*args).as_text()


def _serve_flow_requests(gw_cls, sampler, cond, x0s, budgets, **gw_kw):
    """Submit one request per noise row, drain, and return the latents in
    submission order (host arrays) and the seconds from first submit to the
    last settled sample."""
    import numpy as np

    from repro.serving.gateway import Request

    rows = cond["tokens"]
    gw = gw_cls(sampler, **gw_kw)
    gw.start()
    t0 = time.perf_counter()
    futs = [gw.submit(Request(tokens=rows[i % rows.shape[0]],
                              budget=budgets[i % len(budgets)], x0=x0s[i]))
            for i in range(x0s.shape[0])]
    gw.shutdown(timeout=600.0)
    results = [f.result(timeout=600.0) for f in futs]
    lat = [np.asarray(r.latents) for r in results]
    dt = time.perf_counter() - t0
    for i, r in enumerate(results):
        want = budgets[i % len(budgets)]
        if r.meta["served_budget"] != want:
            raise AssertionError(f"request {i}: served budget "
                                 f"{r.meta['served_budget']} != {want}")
        if lat[i].shape != x0s.shape[1:] or not np.isfinite(lat[i]).all():
            raise AssertionError(f"request {i}: non-finite or misshapen "
                                 f"sample {lat[i].shape}")
    return lat, dt


def flow_phase(ctx) -> None:
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.core.schedulers import get_scheduler
    from repro.data.synthetic import DataConfig, SyntheticTokens
    from repro.kernels.ns_update.ops import fused_ns_update, make_update_fn
    from repro.kernels.ns_update.ref import ns_update_ref
    from repro.launch import serve
    from repro.models import model as M
    from repro.serving import AnytimeFlowSampler, SolverZoo
    from repro.serving.continuous import ContinuousGateway
    from repro.serving.gateway import Gateway, Request

    args = _serve_args(ctx["smoke"], ["--solver-artifact",
                                      os.path.join(OUT_DIR, "anytime.msgpack")])
    cfg = get_config(args.arch, smoke=args.smoke)
    sched = get_scheduler(args.scheduler)
    params, dt = _timed(lambda: serve.init_params(args, cfg))
    ctx["params"], ctx["cfg"] = params, cfg
    log(f"flow: yi-6b params {sum(x.size for x in jax.tree.leaves(params)):,}"
        f" (n_layers={cfg.n_layers} d_model={cfg.d_model} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab}) initialised in {dt:.2f} s")
    cond = SyntheticTokens(cfg, DataConfig(batch_size=args.batch,
                                           seq_len=args.seq,
                                           seed=args.seed)).batch(0)
    field = M.velocity_field(params, cfg, sched, cond, cfg_scale=args.cfg_scale)

    # distil on a miss, exactly as serve.py's zoo does
    shutil.rmtree(OUT_DIR, ignore_errors=True)
    os.makedirs(OUT_DIR)
    spec = serve.requested_spec(args)
    zoo = SolverZoo(capacity=args.zoo_capacity,
                    distill_fn=lambda s: serve.distill_artifact(args, field,
                                                                cfg, s),
                    scan_dirs=[OUT_DIR])
    art, dt = _timed(lambda: zoo.get(spec, log=log))
    if zoo.stats.distills != 1:
        raise AssertionError(f"expected one distillation, zoo: {zoo.stats}")
    log(f"flow: distilled {spec.mode} budgets={art.budgets} "
        f"val PSNR {art.val_psnr:.2f} dB in {dt:.2f} s")

    def no_retraining(_spec):
        raise AssertionError("the saved artifact was not reloaded")

    zoo2 = SolverZoo(capacity=args.zoo_capacity, distill_fn=no_retraining,
                     scan_dirs=[OUT_DIR])
    art2 = zoo2.get(spec, log=log)
    if (zoo2.stats.loads, zoo2.stats.distills) != (1, 0):
        raise AssertionError(f"reload was not a pure load: {zoo2.stats}")
    for a, b in zip(jax.tree.leaves(art.params), jax.tree.leaves(art2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    log("flow: artifact reloaded from disk, no retraining")

    # the Pallas update against XLA's weighted sum, at a serving shape
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    n = max(BUDGETS)
    x0 = jax.random.normal(ks[0], (8, args.seq, cfg.latent_dim))
    U = jax.random.normal(ks[1], (n, 8, args.seq, cfg.latent_dim))
    a, w = jax.random.normal(ks[2], ()), jax.random.normal(ks[3], (n,))
    rel = _check_rel("ns_update kernel vs XLA",
                     fused_ns_update(x0, U, a, w, use_kernel=True),
                     ns_update_ref(x0, U, a, w), UPDATE_TOL)
    log(f"flow: ns_update kernel vs XLA weighted sum relative L2 {rel:.3e} "
        f"(tol {UPDATE_TOL:g})")

    samplers = {
        "xla": AnytimeFlowSampler.from_artifact(art2, params=params, cfg=cfg,
                                                sched=sched),
        "kernel": AnytimeFlowSampler.from_artifact(
            art2, params=params, cfg=cfg, sched=sched,
            update_fn=make_update_fn(use_kernel=True)),
    }
    x0s = jax.random.normal(jax.random.PRNGKey(1000),
                            (args.requests, args.seq, cfg.latent_dim))
    budgets = (2, 8, 4)   # mixed: joins at every exit boundary
    rows = cond["tokens"]

    def direct(s, i):
        b = budgets[i % len(budgets)]
        return np.asarray(s.sample_from(
            {"tokens": rows[i % rows.shape[0]][None]}, x0s[i][None], b)[0])

    served = {}
    for name, s in samplers.items():
        gw_kw = dict(max_slots=args.max_slots, max_batch=args.max_batch,
                     max_wait_ms=args.max_wait_ms,
                     mixed_budget_policy=args.mixed_budget_policy)
        _, cold = _serve_flow_requests(ContinuousGateway, s, cond, x0s,
                                       budgets, **gw_kw)
        served[name], warm = _serve_flow_requests(ContinuousGateway, s, cond,
                                                  x0s, budgets, **gw_kw)
        log(f"flow[{name}]: {args.requests} requests, budgets {budgets} "
            f"cycled, through ContinuousGateway: {cold:.2f} s cold "
            f"(compiles included), {warm:.2f} s warm; every sample finite")
    want = [direct(samplers["xla"], i) for i in range(args.requests)]
    for name in samplers:
        rel = [_check_rel(f"{name} request {i} vs direct sampler",
                          served[name][i], want[i], BF16_REL_TOL)
               for i in range(args.requests)]
        log(f"flow[{name}]: served vs direct sampler (batch of 1) relative "
            f"L2 max {max(rel):.3e} mean {np.mean(rel):.3e} "
            f"(tol {BF16_REL_TOL:g})")
    rel = [_check_rel(f"request {i} kernel vs XLA update",
                      served["kernel"][i], served["xla"][i], BF16_REL_TOL)
           for i in range(args.requests)]
    log(f"flow: served with kernel update vs XLA update relative L2 max "
        f"{max(rel):.3e} (tol {BF16_REL_TOL:g})")

    # the serving contract: a lone request (bucket 1) through the flush
    # gateway is bit-identical to the direct sampler on the same noise
    s = samplers["xla"]
    gw = Gateway(s, max_batch=args.max_batch, max_wait_ms=args.max_wait_ms,
                 mixed_budget_policy="never")
    gw.start()
    i = budgets.index(8)
    fut = gw.submit(Request(tokens=rows[i % rows.shape[0]], budget=8,
                            x0=x0s[i]))
    gw.shutdown(timeout=600.0)
    got = np.asarray(fut.result(timeout=600.0).latents)
    np.testing.assert_array_equal(got, want[i])
    log("flow: lone flush-gateway sample bit-identical to sample_from")

    if ctx["tpu"]:
        one = ({"tokens": rows[i % rows.shape[0]][None]}, x0s[i][None])
        for name, kernel in (("kernel", True), ("xla", False)):
            samplers[name].sample_from(*one, 8)    # builds the program
            fn = samplers[name]._per_budget[8]
            if _has_kernel(fn, params, *one) != kernel:
                raise AssertionError(f"flow[{name}]: tpu_custom_call "
                                     f"{'missing from' if kernel else 'in'}"
                                     " the lowered sampling program")
        log("flow: lowered sampling program has tpu_custom_call with "
            "--kernel-update, none without")


def decode_phase(ctx) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.flash_attention.ops import paged_attend
    from repro.models import model as M
    from repro.serving import DecodeEngine
    from repro.serving.decode import DecodeGateway, DecodeRequest

    params, cfg = ctx["params"], ctx["cfg"]
    rng = np.random.default_rng(0)
    lengths, max_tokens = (5, 23, 70, 12), (16, 8, 24, 12)
    prompts = [rng.integers(1, cfg.vocab, size=n).tolist() for n in lengths]
    tokens = {}
    for kernel in (True, False):
        engine = DecodeEngine(params=params, cfg=cfg, page_size=16,
                              paged_kernel=kernel)
        gw = DecodeGateway(engine, max_slots=4, cache_slots=128,
                           key=jax.random.PRNGKey(0))
        gw.start()
        t0 = time.perf_counter()
        futs = [gw.submit(DecodeRequest(prompt=p, max_tokens=m))
                for p, m in zip(prompts, max_tokens)]
        gw.shutdown(timeout=600.0)
        out = [np.asarray(f.result(timeout=600.0).tokens) for f in futs]
        dt = time.perf_counter() - t0
        tokens[kernel] = out
        name = "paged kernel" if kernel else "dense gather"
        log(f"decode[{name}]: {len(prompts)} prompts of lengths {lengths}, "
            f"{sum(len(t) for t in out)} tokens in {dt:.2f} s "
            "(compiles included)")
        if ctx["tpu"]:
            state = engine.init_slot_state(4, 128)
            args = (params, jnp.zeros((4,), jnp.int32), state,
                    jnp.ones((4,), bool))
            if _has_kernel(engine._step_slots, *args) != kernel:
                raise AssertionError(f"decode[{name}]: tpu_custom_call "
                                     f"{'missing from' if kernel else 'in'}"
                                     " the lowered step program")
    if ctx["tpu"]:
        log("decode: lowered step program has tpu_custom_call with "
            "--paged-kernel, none without")

    # where the two attention paths pick different greedy tokens, a full
    # forward of the shared prefix must rate the two tokens a bf16 tie
    forward = jax.jit(lambda p, t: M.lm_apply(p, cfg, {"tokens": t},
                                              last_only=True))
    for i, (k, d) in enumerate(zip(tokens[True], tokens[False])):
        if len(k) != max_tokens[i] or len(d) != max_tokens[i]:
            raise AssertionError(f"decode prompt {i}: {len(k)} / {len(d)} "
                                 f"tokens, asked for {max_tokens[i]}")
        part = next((j for j in range(len(k)) if k[j] != d[j]), None)
        if part is None:
            log(f"decode prompt {i}: all {len(k)} greedy tokens equal")
            continue
        prefix = jnp.asarray([prompts[i] + d[:part].tolist()], jnp.int32)
        logits = np.asarray(forward(params, prefix), np.float32).reshape(-1)
        top = float(logits.max())
        ulp = 2.0 ** (np.floor(np.log2(abs(top))) - 7)   # bf16 step at top
        gap = abs(float(logits[k[part]] - logits[d[part]]))
        if gap > LOGIT_TIE_ULPS * ulp or top - max(
                logits[k[part]], logits[d[part]]) > LOGIT_TIE_ULPS * ulp:
            raise AssertionError(
                f"decode prompt {i}: paths part at token {part} "
                f"({k[part]} vs {d[part]}) without a logit tie: logits "
                f"{logits[k[part]]:.4f} / {logits[d[part]]:.4f}, top "
                f"{top:.4f}, bf16 step {ulp:g}")
        log(f"decode prompt {i}: first {part} of {len(k)} greedy tokens "
            f"equal; at token {part} the paths pick {k[part]} / {d[part]}, "
            f"whose logits {logits[k[part]]:.4f} / {logits[d[part]]:.4f} "
            f"tie within {LOGIT_TIE_ULPS} bf16 steps ({ulp:g})")

    # the kernel against its dense-gather oracle at yi-6b decode widths
    B, KV, hd, ps, nb = 4, cfg.n_kv_heads, cfg.resolved_head_dim, 16, 8
    G = cfg.n_heads // KV
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (B, KV, G, hd), jnp.bfloat16)
    pool = (1 + B * nb, KV, ps, hd)
    kp = jax.random.normal(ks[1], pool)
    vp = jax.random.normal(ks[2], pool)
    table = (1 + jax.random.permutation(ks[3], B * nb)).reshape(B, nb)
    lengths = jnp.asarray([1, 37, 100, nb * ps], jnp.int32)
    got = paged_attend(q, kp, vp, table, lengths)
    with jax.default_matmul_precision("float32"):
        ref = paged_attend(q, kp, vp, table, lengths, use_kernel=False)
    rel = _check_rel("paged attention kernel vs oracle", got, ref, ATTN_TOL)
    log(f"decode: paged attention kernel vs dense-gather oracle relative "
        f"L2 {rel:.3e} (tol {ATTN_TOL:g})")


def four_chip_phase(ctx) -> None:
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.core.anytime import init_anytime
    from repro.core.schedulers import get_scheduler
    from repro.data.synthetic import DataConfig, SyntheticTokens
    from repro.launch import serve
    from repro.models import model as M
    from repro.serving import AnytimeFlowSampler
    from repro.serving.continuous import ContinuousGateway
    from repro.serving.sharded import serving_mesh

    if len(jax.devices()) != 4:
        raise AssertionError(f"--four-chips needs 4 devices, found "
                             f"{len(jax.devices())}")
    args = _serve_args(ctx["smoke"], ["--mesh", "host"])
    cfg = get_config(args.arch, smoke=args.smoke)
    sched = get_scheduler(args.scheduler)
    cond = SyntheticTokens(cfg, DataConfig(batch_size=args.batch,
                                           seq_len=args.seq,
                                           seed=args.seed)).batch(0)
    x0s = jax.random.normal(jax.random.PRNGKey(1000),
                            (args.requests, args.seq, cfg.latent_dim))
    budgets = (2, 8, 4)
    # the sharded path is under test, not the solver: the undistilled
    # anytime init serves as well as a distilled one
    params, dt = _timed(lambda: serve.init_params(args, cfg))
    log(f"four-chips: yi-6b params on {jax.devices()[0]} in {dt:.2f} s")
    solver = init_anytime(M.velocity_field(params, cfg, sched, cond),
                          BUDGETS)

    def sampler(p):
        return AnytimeFlowSampler(params=p, cfg=cfg, sched=sched,
                                  anytime=solver, budgets=BUDGETS)

    gw_kw = dict(max_slots=args.max_slots, max_batch=args.max_batch,
                 max_wait_ms=args.max_wait_ms)
    single = sampler(params)
    one, cold = _serve_flow_requests(ContinuousGateway, single, cond, x0s,
                                     budgets, **gw_kw)
    one, warm = _serve_flow_requests(ContinuousGateway, single, cond, x0s,
                                     budgets, **gw_kw)
    log(f"four-chips[1 device]: {args.requests} requests in {cold:.2f} s "
        f"cold, {warm:.2f} s warm")
    # a second whole copy on device 0 would not fit beside its shard: the
    # sharded run places the host copy
    host = jax.device_get(params)
    del params, single
    gc.collect()

    mesh = serving_mesh(args.mesh)
    sharded = sampler(host)
    four, cold = _serve_flow_requests(ContinuousGateway, sharded, cond, x0s,
                                      budgets, mesh=mesh, **gw_kw)
    leaves = jax.tree.leaves(sharded.params)
    if any(len(x.sharding.device_set) != 4 for x in leaves):
        raise AssertionError("some params do not span the 4 devices")
    split = [x for x in leaves if x.sharding.shard_shape(x.shape) != x.shape]
    per_dev = sum(x.addressable_shards[0].data.nbytes for x in leaves)
    total = sum(x.nbytes for x in leaves)
    log(f"four-chips: mesh {dict(mesh.shape)} over "
        f"{sorted(d.id for d in mesh.devices.flat)}; params span 4 devices, "
        f"{len(split)}/{len(leaves)} leaves split, {per_dev / 1e9:.2f} GB of "
        f"{total / 1e9:.2f} GB on device 0")
    four, warm = _serve_flow_requests(ContinuousGateway, sharded, cond, x0s,
                                      budgets, mesh=mesh, **gw_kw)
    log(f"four-chips[4 devices]: {args.requests} requests in {cold:.2f} s "
        f"cold, {warm:.2f} s warm")
    rel = [_check_rel(f"request {i} 4 devices vs 1", four[i], one[i],
                      BF16_REL_TOL) for i in range(args.requests)]
    err = max(float(np.max(np.abs(f - o))) for f, o in zip(four, one))
    log(f"four-chips: sharded vs one-device relative L2 max {max(rel):.3e} "
        f"mean {np.mean(rel):.3e} (tol {BF16_REL_TOL:g}), max |diff| "
        f"{err:.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded flow gateway on a four-chip "
                         "host against one device")
    ap.add_argument("--smoke", action="store_true",
                    help="CPU rehearsal at smoke sizes (not a chip result)")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(f"device: platform={device['platform']} kind={device['kind']} "
        f"count={device['count']}")
    if device["platform"] != "tpu" and not args.smoke:
        print("chip_smoke: no TPU found; there is no CPU fallback "
              "(--smoke rehearses on the CPU)", file=sys.stderr)
        return 1
    ctx = {"smoke": args.smoke, "tpu": device["platform"] == "tpu"}
    phases = ([("four-chips", four_chip_phase)] if args.four_chips
              else [("flow", flow_phase), ("decode", decode_phase)])
    failed = []
    for name, phase in phases:
        t0 = time.perf_counter()
        try:
            phase(ctx)
        except Exception:
            traceback.print_exc()
            failed.append(name)
            log(f"phase {name}: FAILED after {time.perf_counter() - t0:.1f} s")
            continue
        log(f"phase {name}: ok in {time.perf_counter() - t0:.1f} s")
    if failed:
        log(f"chip_smoke: failed phases: {', '.join(failed)}")
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
