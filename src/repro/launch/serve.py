"""Serving launcher: BNS-accelerated flow sampling or autoregressive decode.

  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --mode flow \
      --nfe 8 --batch 8 --seq 16 [--ckpt /path/step_N.msgpack] \
      [--solver-artifact /path/solver.msgpack]
  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --mode flow \
      --budgets 4,8,16 --request-budgets 4,16,8   # anytime: one artifact
  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-7b --mode decode \
      --batch 4 --steps 32
  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --mode decode \
      --gateway --max-slots 4 --requests 8 --decode-lengths 32,8,16

Flow mode routes solver acquisition through a ``SolverZoo``: a saved
``SolverArtifact`` (--solver-artifact, or anything indexed by --zoo-dir) is
loaded without retraining; a miss distills lazily (Algorithm 2 on freshly
generated RK45 pairs), saves the artifact, and serves from the reloaded copy
— so every serving session exercises the artifact round-trip.

With --budgets the solver is a single anytime artifact whose early exits
serve every listed NFE; each request's budget (--request-budgets, cycled)
routes to the matching exit. A requested --nfe / request budget the artifact
does not serve is resolved to the nearest served budget with a WARNING, or
rejected when --strict-nfe is set — never silently ignored.

--gateway serves the same traffic through ``repro.serving.Gateway``: each
request becomes a single-sample submit, the batcher coalesces them by
resolved budget into padded fixed-size batches (--max-batch, --max-wait-ms),
mixed-budget flushes may ride the anytime shared trajectory
(--mixed-budget-policy), and --mesh shards the backbone over a serving mesh
(params via distributed.sharding, batches along the data axes). Each
response prints its (requested, served) budget pair — drift is recorded in
metadata, not just warned. --kernel-update routes the solver update through
the Pallas ns_update kernel. --fleet N federates N per-host gateways behind
one ``repro.serving.fleet.FleetGateway`` (sharded request queue, affinity
routing, work stealing) — the summary adds a fleet stats line.

--slo attaches an ``SLOConfig`` to every gateway tier: --deadline-ms /
--priority stamp each request, infeasible submits fast-reject at the door
(``AdmissionRejected``), queued requests past their deadline are shed
(``DeadlineExceeded``), planning is urgency-ordered, and the continuous
tier preempts strictly-lower-priority slots at anytime exit boundaries.
--stream switches submits to ``submit_stream`` (per-exit-boundary partials
for flow, per-token chunks for decode; the terminal result is bit-identical
to the plain submit). --profile tuned re-executes once under the serving
XLA flag set with tcmalloc preloaded (see ``repro.launch.profile``).

Every gateway mode shares one telemetry plane (``repro.observability``):
--metrics-port serves live Prometheus text + JSON registry snapshots,
--stats-interval N prints a periodic one-line summary through the SAME
formatter that renders each mode's final stats line, --metrics-json dumps
the final snapshot, and --trace-jsonl records per-request lifecycle spans
(submit -> route -> steal -> dispatch -> settle) to a JSONL file.

Decode mode serves batched greedy decode (jit'd multi-token scan). With
--gateway it becomes a multi-user continuous-batching service
(``repro.serving.decode.DecodeGateway``): each request is one prompt
submitted to a fixed pool of --max-slots state slots; finished sequences
free their slot and queued prompts are admitted at the very next engine
step, bit-identical to decoding each prompt alone. --decode-lengths cycles
per-request max_tokens (mixed output lengths are where continuous refill
beats run-to-completion batching). --page-size switches the KV cache to a
shared paged pool (--paged-kernel routes attention through the Pallas
paged-attention kernel), --prefill-chunk controls batched chunked prompt
prefill (0 = token-by-token teacher forcing), and --temperature/--top-k/
--top-p sample instead of greedy argmax (temperature 0 = greedy).
"""
from __future__ import annotations

import argparse
import os
import tempfile
import time

import jax

from repro.checkpoint import checkpointer
from repro.configs import get_config
from repro.core.bns import BNSTrainConfig
from repro.core.parametrization import as_partial
from repro.core.rk45 import rk45_solve
from repro.core.schedulers import get_scheduler
from repro.data.synthetic import DataConfig, SyntheticTokens
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.observability import (
    MetricsServer,
    StatsPrinter,
    TraceRecorder,
    format_stats_line,
)
from repro.serving import (
    AdmissionRejected,
    AnytimeFlowSampler,
    DeadlineExceeded,
    DecodeEngine,
    FlowSampler,
    SLOConfig,
    SolverZoo,
    greedy_demo,
)
from repro.solvers import SolverArtifact, SolverSpec

DEFAULT_NFE = 8


def _start_telemetry(args, gw, prefix: str) -> list:
    """--metrics-port / --stats-interval surfaces around a live gateway.

    Returns the stop callables to run after the traffic loop."""
    stop = []
    if args.metrics_port is not None:
        srv = MetricsServer(gw.metrics_snapshot,
                            port=args.metrics_port).start()
        print(f"metrics: http://127.0.0.1:{srv.port}/metrics "
              "(+ /metrics.json)")
        stop.append(srv.stop)
    if args.stats_interval > 0:
        printer = StatsPrinter(
            lambda: format_stats_line(gw.stats(), prefix=prefix),
            args.stats_interval).start()
        stop.append(printer.stop)
    return stop


def _finish_telemetry(args, gw) -> None:
    """Dump --metrics-json / --trace-jsonl after the traffic loop."""
    import json

    if args.metrics_json:
        with open(args.metrics_json, "w") as fh:
            json.dump(gw.metrics_snapshot(), fh, indent=2, sort_keys=True)
        print(f"metrics snapshot written to {args.metrics_json}")
    rec = getattr(gw, "recorder", None)
    if args.trace_jsonl and rec:
        n = rec.export_jsonl(args.trace_jsonl)
        print(f"trace: {n} events written to {args.trace_jsonl}")


def requested_spec(args) -> SolverSpec:
    """The solver the CLI asks for: anytime over --budgets, else fixed-NFE BNS."""
    if args.budgets:
        return SolverSpec(name="midpoint", mode="anytime",
                          budgets=args.budgets, cfg_scale=args.cfg_scale)
    return SolverSpec(name="euler", nfe=args.nfe or DEFAULT_NFE,
                      cfg_scale=args.cfg_scale, mode="bns")


def distill_artifact(args, field, cfg, spec: SolverSpec) -> SolverArtifact:
    """Algorithm 2 on fresh RK45 pairs; returns the saved-and-reloaded artifact."""
    what = (f"anytime solver (budgets={spec.budgets})" if spec.budgets
            else f"BNS solver (NFE={spec.nfe})")
    print(f"distilling {what} ...")
    # the field rides in as an argument, so the backbone weights are inputs
    # of the solve program, not constants baked into it
    solve = jax.jit(lambda u, x: rk45_solve(u, x, rtol=1e-5, atol=1e-5).x1)
    u = as_partial(field.fn)
    k_tr, k_val = jax.random.split(jax.random.PRNGKey(args.seed + 1))
    shape = (args.batch, args.seq, cfg.latent_dim)
    x0 = jax.random.normal(k_tr, shape)
    x0v = jax.random.normal(k_val, shape)  # held-out: no train/val leak
    res = spec.distill(field, (x0, solve(u, x0)), (x0v, solve(u, x0v)),
                       BNSTrainConfig(lr=1e-3, lr_schedule="cosine",
                                      iterations=args.bns_iters, val_every=100,
                                      batch_size=args.batch))
    print(f"solver ready: {res.num_parameters} params, "
          f"val PSNR {res.val_psnr:.2f} dB, {res.wall_seconds:.0f}s")
    path = args.solver_artifact or os.path.join(
        tempfile.mkdtemp(prefix="bns_solver_"), "solver.msgpack")
    res.artifact(provenance={"arch": args.arch, "scheduler": args.scheduler,
                             "seed": args.seed,
                             "bns_iters": args.bns_iters}).save(path)
    print(f"solver artifact saved to {path}")
    return SolverArtifact.load(path)


def _resolve_budget(artifact: SolverArtifact, nfe: int, strict: bool,
                    warned: set) -> int:
    """Route a requested NFE to a budget the artifact serves.

    Exact match passes through; otherwise --strict-nfe rejects, and the
    default picks the nearest served budget with a one-time WARNING per
    distinct mismatch (the old behavior silently ignored --nfe).
    """
    if nfe in artifact.budgets:
        return nfe
    if strict:
        raise SystemExit(f"--strict-nfe: requested NFE {nfe} but the "
                         f"artifact serves {artifact.budgets}")
    near = artifact.nearest_budget(nfe)
    if nfe not in warned:
        warned.add(nfe)
        print(f"WARNING: requested NFE {nfe} not served by the artifact "
              f"(budgets {artifact.budgets}); using nearest budget {near}")
    return near


def init_params(args, cfg):
    """Backbone weights: random from --seed, then --ckpt when given. The
    init is jitted — eager, it would hold a float32 copy of every layer at
    once, which at published widths does not fit one chip."""
    params = jax.jit(M.init_params, static_argnums=1)(
        jax.random.PRNGKey(args.seed), cfg)
    if args.ckpt:
        params = checkpointer.restore(args.ckpt, params)
        print(f"restored params from {args.ckpt}")
    return params


def serve_flow(args) -> None:
    cfg = get_config(args.arch, smoke=args.smoke)
    sched = get_scheduler(args.scheduler)
    params = init_params(args, cfg)

    data = SyntheticTokens(cfg, DataConfig(batch_size=args.batch,
                                           seq_len=args.seq, seed=args.seed))
    cond = data.batch(0)
    field = M.velocity_field(params, cfg, sched, cond, cfg_scale=args.cfg_scale)

    scan_dirs = [d for d in (args.zoo_dir,
                             os.path.dirname(args.solver_artifact)
                             if args.solver_artifact else None) if d]
    zoo = SolverZoo(capacity=args.zoo_capacity,
                    distill_fn=lambda spec: distill_artifact(args, field,
                                                              cfg, spec),
                    scan_dirs=scan_dirs)
    if args.solver_artifact and os.path.exists(args.solver_artifact):
        artifact = zoo.put(SolverArtifact.load(args.solver_artifact))
        print(f"loaded solver artifact {args.solver_artifact}: "
              f"{artifact.spec.mode}/{artifact.spec.name} "
              f"budgets={artifact.budgets}, "
              f"val PSNR {artifact.val_psnr:.2f} dB (no retraining)")
        for key, want in [("arch", args.arch), ("scheduler", args.scheduler)]:
            have = artifact.provenance.get(key)
            if have is not None and have != want:
                print(f"WARNING: artifact was distilled for {key}={have!r} "
                      f"but serving {key}={want!r} — samples will be degraded")
        if args.budgets and tuple(sorted(args.budgets)) != artifact.budgets:
            print(f"WARNING: --budgets {','.join(map(str, args.budgets))} "
                  f"ignored; the loaded artifact serves {artifact.budgets}")
    else:
        artifact = zoo.get(requested_spec(args), log=print)

    update_fn = None
    if args.kernel_update:
        from repro.kernels.ns_update.ops import make_update_fn

        update_fn = make_update_fn(use_kernel=True)
    anytime = artifact.kind == "anytime"
    if anytime:
        sampler = AnytimeFlowSampler.from_artifact(artifact, params=params,
                                                   cfg=cfg, sched=sched,
                                                   update_fn=update_fn)
    else:
        sampler = FlowSampler.from_artifact(artifact, params=params,
                                            cfg=cfg, sched=sched,
                                            update_fn=update_fn)
    warned: set = set()
    if args.request_budgets:
        request_budgets = args.request_budgets
    elif args.nfe is not None:
        # an explicit --nfe is a request, never silently ignored: it routes
        # through _resolve_budget (nearest-with-warning or --strict-nfe)
        request_budgets = (args.nfe,)
    else:
        request_budgets = artifact.budgets
    if args.gateway:
        _serve_gateway(args, sampler, cond, request_budgets)
    else:
        for req in range(args.requests):
            nfe = _resolve_budget(artifact,
                                  request_budgets[req % len(request_budgets)],
                                  args.strict_nfe, warned)
            t0 = time.time()
            key = jax.random.PRNGKey(1000 + req)
            latents = (sampler.sample(cond, key, budget=nfe) if anytime
                       else sampler.sample(cond, key))
            tokens = jax.block_until_ready(sampler.nearest_tokens(latents))
            print(f"request {req}: sampled {tokens.shape} in "
                  f"{(time.time()-t0)*1e3:.0f} ms ({nfe} NFE)")
    print(f"zoo stats: hits={zoo.stats.hits} misses={zoo.stats.misses} "
          f"loads={zoo.stats.loads} distills={zoo.stats.distills}")


def _serve_gateway(args, sampler, cond, request_budgets) -> None:
    """Multi-user serving: every request is one coalesced-batch submit."""
    from repro.serving.continuous import ContinuousGateway
    from repro.serving.fleet import FleetGateway
    from repro.serving.gateway import Gateway, Request
    from repro.serving.sharded import serving_mesh

    from repro.serving.tiers import ShapeLadder

    recorder = TraceRecorder() if args.trace_jsonl else None
    slo = (SLOConfig(slack_ms=args.slo_slack,
                     default_cost_ms=args.slo_default_cost_ms)
           if args.slo else None)
    tiers = ShapeLadder.parse(args.tiers) if args.tiers else None

    def make_host(rec=None):
        # the solver artifact is tiny, so every fleet host serves the SAME
        # sampler object — replication is free, distribution is the work
        if args.continuous:
            return ContinuousGateway(
                sampler, max_slots=args.max_slots, max_batch=args.max_batch,
                max_wait_ms=args.max_wait_ms,
                mixed_budget_policy=args.mixed_budget_policy,
                strict_nfe=args.strict_nfe, mesh=serving_mesh(args.mesh),
                recorder=rec, slo=slo, tiers=tiers)
        return Gateway(sampler, max_batch=args.max_batch,
                       max_wait_ms=args.max_wait_ms,
                       mixed_budget_policy=args.mixed_budget_policy,
                       strict_nfe=args.strict_nfe, mesh=serving_mesh(args.mesh),
                       recorder=rec, slo=slo, tiers=tiers)

    if args.fleet > 1:
        # hosts get the recorder through federate() so every hop carries
        # its host name
        gw = FleetGateway({f"h{i}": make_host() for i in range(args.fleet)},
                          recorder=recorder)
    else:
        gw = make_host(rec=recorder)
    gw.start()
    stop_telemetry = _start_telemetry(args, gw, "gateway stats")
    futures = []
    for req in range(args.requests):
        nfe = request_budgets[req % len(request_budgets)]
        row = cond["tokens"][req % cond["tokens"].shape[0]]
        kw = dict(tokens=row, budget=nfe, key=jax.random.PRNGKey(1000 + req),
                  deadline_ms=args.deadline_ms, priority=args.priority)
        try:
            futures.append(gw.submit_stream(**kw) if args.stream
                           else gw.submit(Request(**kw)))
        except AdmissionRejected as e:
            print(f"request {req}: REJECTED at admission ({e})")
            futures.append(None)
        except ValueError as e:
            raise SystemExit(f"--strict-nfe: {e}")
    gw.shutdown()
    for i, fut in enumerate(futures):
        if fut is None:
            continue
        try:
            partials = 0
            if args.stream:
                chunks = fut.chunks(timeout=60.0)
                partials = sum(1 for c in chunks if not c.final)
                meta = chunks[-1].payload.meta
            else:
                meta = fut.result().meta
        except DeadlineExceeded:
            print(f"request {i}: SHED (deadline exceeded in queue)")
            continue
        drift = ("" if meta["requested_budget"] == meta["served_budget"]
                 else f" (requested {meta['requested_budget']})")
        print(f"request {i}: served {meta['served_budget']} NFE{drift}, "
              f"wait {meta['wait_ms']:.1f} ms, "
              f"batch {meta['batch_real']}/{meta['batch_padded']}"
              + (" [mixed]" if meta["mixed"] else "")
              + (f", {partials} streamed partials" if args.stream else ""))
    for fn in stop_telemetry:
        fn()
    stats = gw.stats()
    print(format_stats_line(stats, prefix="gateway stats"))
    if stats.get("cost_est_samples"):
        # admission cost-model calibration: how far the wait estimates
        # stamped at submit landed from the actual settle times
        print(f"admission cost model: |estimate-actual| mean "
              f"{stats['cost_est_error_mean_ms']:.2f} ms / p95 "
              f"{stats['cost_est_error_p95_ms']:.2f} ms over "
              f"{stats['cost_est_samples']} deadline requests")
    _finish_telemetry(args, gw)


def serve_decode(args) -> None:
    cfg = get_config(args.arch, smoke=args.smoke)
    params = init_params(args, cfg)
    engine = DecodeEngine(params=params, cfg=cfg, window=args.window,
                          page_size=args.page_size,
                          paged_kernel=args.paged_kernel)
    if args.gateway:
        _serve_decode_gateway(args, engine, cfg)
        return
    tokens, dt = greedy_demo(engine, args.batch, args.steps, args.slots)
    print(f"decoded {args.steps} tokens x {args.batch} seqs "
          f"({dt:.1f} ms/token); first row: {tokens[0, :8].tolist()}")


def _serve_decode_gateway(args, engine, cfg) -> None:
    """Continuous decode batching: every request is one prompt -> state slot."""
    from repro.serving.decode import DecodeGateway, DecodeRequest
    from repro.serving.engine import SamplingParams

    lengths = args.decode_lengths or (args.steps, max(1, args.steps // 2))
    sampling = None
    if args.temperature > 0.0 or args.top_k > 0 or args.top_p < 1.0:
        sampling = SamplingParams(temperature=args.temperature,
                                  top_k=args.top_k, top_p=args.top_p)
    recorder = TraceRecorder() if args.trace_jsonl else None
    gw = DecodeGateway(engine, max_slots=args.max_slots,
                       cache_slots=args.slots,
                       prefill_chunk=args.prefill_chunk,
                       key=jax.random.PRNGKey(args.seed),
                       recorder=recorder,
                       slo=(SLOConfig(
                           slack_ms=args.slo_slack,
                           default_cost_ms=args.slo_default_cost_ms)
                           if args.slo else None))
    gw.start()
    stop_telemetry = _start_telemetry(args, gw, "decode gateway stats")
    futures = []
    for req in range(args.requests):
        prompt = [(3 * req + 1) % cfg.vocab, (5 * req + 2) % cfg.vocab]
        kw = dict(prompt=prompt, max_tokens=lengths[req % len(lengths)],
                  sampling=sampling, deadline_ms=args.deadline_ms,
                  priority=args.priority)
        try:
            futures.append(gw.submit_stream(**kw) if args.stream
                           else gw.submit(DecodeRequest(**kw)))
        except AdmissionRejected as e:
            print(f"request {req}: REJECTED at admission ({e})")
            futures.append(None)
    gw.shutdown()
    for i, fut in enumerate(futures):
        if fut is None:
            continue
        try:
            streamed = 0
            if args.stream:
                chunks = fut.chunks(timeout=60.0)
                streamed = sum(1 for c in chunks if not c.final)
                meta = chunks[-1].payload.meta
            else:
                meta = fut.result().meta
        except DeadlineExceeded:
            print(f"request {i}: SHED (deadline exceeded in queue)")
            continue
        print(f"request {i}: {meta['new_tokens']} tokens "
              f"({meta['finish_reason']}), wait {meta['wait_ms']:.1f} ms, "
              f"slot {meta['slot']}, join_step {meta['join_step']}"
              + (f", {streamed} streamed tokens" if args.stream else ""))
    for fn in stop_telemetry:
        fn()
    print(format_stats_line(gw.stats(), prefix="decode gateway stats"))
    _finish_telemetry(args, gw)


def _budget_list(text: str) -> tuple[int, ...]:
    try:
        budgets = tuple(int(tok) for tok in text.split(",") if tok.strip())
    except ValueError:
        raise argparse.ArgumentTypeError(f"bad budget list {text!r}")
    if not budgets or any(b < 1 for b in budgets):
        raise argparse.ArgumentTypeError(f"bad budget list {text!r}")
    return budgets


def build_parser() -> argparse.ArgumentParser:
    """The full serve.py CLI. A separate builder so tests (and the docs
    drift guard in ``tests/test_docs.py``) can enumerate every flag
    without running the launcher."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--mode", choices=["flow", "decode"], default="flow")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--scheduler", default="fm_ot")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--solver-artifact", default=None,
                    help="load the solver from this artifact if it exists; "
                         "otherwise distill and save it here")
    ap.add_argument("--nfe", type=int, default=None,
                    help="requested NFE budget (default: the artifact's own; "
                         f"distillation defaults to {DEFAULT_NFE})")
    ap.add_argument("--budgets", type=_budget_list, default=None,
                    help="serve an anytime solver at these NFE budgets, "
                         "e.g. 4,8,16 (one shared artifact, per-request "
                         "budget routing)")
    ap.add_argument("--request-budgets", type=_budget_list, default=None,
                    help="per-request NFE budgets, cycled over --requests "
                         "(default: cycle the artifact's budgets)")
    ap.add_argument("--strict-nfe", action="store_true",
                    help="reject budgets the artifact does not serve instead "
                         "of routing to the nearest one")
    ap.add_argument("--zoo-dir", default=None,
                    help="scan this directory for saved solver artifacts")
    ap.add_argument("--zoo-capacity", type=int, default=4)
    ap.add_argument("--gateway", action="store_true",
                    help="serve requests through the coalescing batch "
                         "gateway (one single-sample submit per request)")
    ap.add_argument("--fleet", type=int, default=1,
                    help="gateway: federate this many per-host gateways "
                         "behind one FleetGateway (sharded queue, affinity "
                         "routing, work stealing); 1 = single gateway")
    ap.add_argument("--max-batch", type=int, default=8,
                    help="gateway: coalesce at most this many requests")
    ap.add_argument("--max-wait-ms", type=float, default=10.0,
                    help="gateway: flush partial batches after this wait")
    ap.add_argument("--continuous", action="store_true",
                    help="gateway: continuous batching — admit requests "
                         "into in-flight anytime trajectories at exit "
                         "boundaries instead of waiting for the next flush "
                         "(needs an anytime --budgets artifact)")
    ap.add_argument("--max-slots", type=int, default=8,
                    help="continuous gateway: trajectory slot count (batch "
                         "width of the shared anytime trajectory); decode "
                         "gateway: sequence slot count")
    ap.add_argument("--decode-lengths", type=_budget_list, default=None,
                    help="decode gateway: per-request max_tokens, cycled "
                         "over --requests (default: --steps and --steps/2 — "
                         "mixed lengths exercise continuous slot refill)")
    ap.add_argument("--page-size", type=int, default=0,
                    help="decode: paged KV cache page size in tokens "
                         "(0 = dense per-slot cache); must divide --slots")
    ap.add_argument("--paged-kernel", action="store_true",
                    help="decode: route paged attention through the Pallas "
                         "paged-attention kernel (interpret mode off-TPU)")
    ap.add_argument("--prefill-chunk", type=int, default=64,
                    help="decode gateway: batched prefill chunk width in "
                         "tokens (0 = legacy token-by-token teacher "
                         "forcing)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="decode gateway: sampling temperature "
                         "(0 = greedy argmax)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="decode gateway: keep only the k most likely "
                         "tokens before sampling (0 = no cap)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="decode gateway: nucleus sampling threshold "
                         "(1.0 = no cap)")
    ap.add_argument("--tiers", default=None,
                    help="gateway modes (flow): shape-tier ladder rungs, "
                         "e.g. 8,16,32 — requests pad their position axis "
                         "to the smallest rung that fits, so near-shapes "
                         "share flush buckets / trajectory slots / fleet "
                         "homes; responses are cropped back (bit-identical "
                         "to the native shape); longer than the top rung "
                         "is rejected at submit (default: exact shapes)")
    ap.add_argument("--mixed-budget-policy", default="auto",
                    choices=["never", "auto", "always"],
                    help="gateway: route multi-budget flushes through the "
                         "anytime shared trajectory (never/auto/always)")
    ap.add_argument("--mesh", default="none",
                    choices=["none", "host", "production", "multipod"],
                    help="gateway: shard the backbone over this serving "
                         "mesh; 'none' = single-device jit, 'host' = every "
                         "local device on the model axis; a mesh the host "
                         "lacks the devices for is an error")
    ap.add_argument("--kernel-update", action="store_true",
                    help="route the NS solver update through the Pallas "
                         "ns_update kernel (interpret mode off-TPU)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="gateway modes: serve /metrics (Prometheus text) "
                         "and /metrics.json on this port while traffic "
                         "runs (0 = ephemeral port, printed at start)")
    ap.add_argument("--metrics-json", default=None,
                    help="gateway modes: write the final registry snapshot "
                         "to this JSON file after the traffic loop")
    ap.add_argument("--stats-interval", type=float, default=0.0,
                    help="gateway modes: print a one-line stats summary "
                         "every N seconds while traffic runs (0 = off); "
                         "the same formatter renders the final line of "
                         "every mode")
    ap.add_argument("--trace-jsonl", default=None,
                    help="gateway modes: record per-request lifecycle "
                         "spans (submit/route/steal/dispatch/settle) and "
                         "export them to this JSONL file")
    ap.add_argument("--slo", action="store_true",
                    help="gateway modes: attach an SLOConfig — fast-reject "
                         "admission control, deadline shedding, urgency-"
                         "ordered planning, and (continuous tier) exit-"
                         "boundary preemption; rejected/shed requests are "
                         "reported per request, not raised")
    ap.add_argument("--slo-slack", type=float, default=0.0,
                    help="with --slo: safety margin in ms subtracted from "
                         "every deadline before the admission/shedding "
                         "comparison (SLOConfig.slack_ms)")
    ap.add_argument("--slo-default-cost-ms", type=float, default=0.0,
                    help="with --slo: per-dispatch cost seeding the "
                         "admission cost model before the first dispatch "
                         "is observed (0 = optimistic: accept everything "
                         "until the histograms warm up); the model then "
                         "self-calibrates, and the final stats report its "
                         "|estimate-actual| error")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="gateway modes: per-request deadline in ms from "
                         "submit; always recorded as goodput vs "
                         "deadline_misses at settle, ENFORCED (admission "
                         "+ shedding) when --slo is set")
    ap.add_argument("--priority", type=int, default=0,
                    help="gateway modes: request priority (higher wins; "
                         "with --slo on the continuous tier, strictly "
                         "higher priority preempts lower at anytime exit "
                         "boundaries)")
    ap.add_argument("--stream", action="store_true",
                    help="gateway modes: submit via submit_stream and "
                         "report streamed increments — per-exit-boundary "
                         "partial latents (flow) or per-token chunks "
                         "(decode); the terminal result is bit-identical "
                         "to the plain submit")
    ap.add_argument("--profile", default="default",
                    choices=["default", "tuned"],
                    help="launch profile: 'tuned' re-execs once with the "
                         "serving XLA flag set merged into XLA_FLAGS and "
                         "tcmalloc preloaded when present (see "
                         "repro.launch.profile)")
    ap.add_argument("--cfg-scale", type=float, default=0.0)
    ap.add_argument("--bns-iters", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=16)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--slots", type=int, default=128)
    ap.add_argument("--window", type=int, default=0)
    ap.add_argument("--requests", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    return ap


def main() -> None:
    enable_compile_cache()
    args = build_parser().parse_args()
    if args.profile != "default":
        from repro.launch.profile import maybe_reexec
        maybe_reexec(args.profile)
    (serve_flow if args.mode == "flow" else serve_decode)(args)


if __name__ == "__main__":
    main()
