#!/usr/bin/env python3
"""Record the small TPU trace the trace-reduction test reads.

    python3 bench/testdata/record.py [out_dir]   # on a TPU

At small sizes: three paged decode steps
(greedy step program) and one chunked prefill through ``DecodeEngine``
with the Pallas paged-attention kernel, and one guided flow sample of
budget 4 through ``AnytimeFlowSampler``, each under the span name the
gateways give it. Writes ``v5e_small.xplane.pb`` and ``v5e_small.json``
(what the test expects to find in it) to ``out_dir``, by default beside
this script.
"""
import dataclasses
import glob
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))

# the decode model of the recording: phi3-medium's head geometry, shrunk
PHI_SMALL = {"n_layers": 2, "d_model": 320, "n_heads": 10, "n_kv_heads": 2,
             "head_dim": 32, "d_ff": 512, "vocab": 256, "latent_dim": 64,
             "dtype": "bfloat16"}


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core.anytime import init_anytime
    from repro.core.schedulers import get_scheduler
    from repro.models import model as M
    from repro.serving import AnytimeFlowSampler, DecodeEngine

    from bench.model import Model

    if jax.devices()[0].platform != "tpu":
        print("record.py: needs a TPU", file=sys.stderr)
        return 2
    cfg = dataclasses.replace(get_config("phi3-medium-14b"), **PHI_SMALL)
    params = jax.jit(M.init_params, static_argnums=1)(jax.random.PRNGKey(0),
                                                       cfg)
    eng = DecodeEngine(params=params, cfg=cfg, page_size=16, paged_kernel=True)
    S = 4
    state = eng.init_slot_state(S, 64, dtype=jnp.bfloat16, total_pages=17)
    table = (1 + np.arange(S * 4)).reshape(S, 4).astype(np.int32)
    state = eng.with_block_table(state, table)
    tok, act = np.ones((S,), np.int32), np.ones((S,), bool)
    toks, lens = np.ones((S, 4), np.int32), np.full((S,), 4, np.int32)
    yi = Model(json.load(open(os.path.join(ROOT, "bench/configs/yi-6b.json"))),
               smoke=True)
    ycfg = dataclasses.replace(yi.program_config(), dtype="bfloat16")
    yparams = jax.jit(M.init_params, static_argnums=1)(
        jax.random.PRNGKey(1), ycfg)
    smp = AnytimeFlowSampler(params=yparams, cfg=ycfg,
                             sched=get_scheduler("fm_ot"),
                             anytime=init_anytime(None, (4, 8, 16)),
                             budgets=(4, 8, 16), cfg_scale=1.5)
    cond = {"tokens": jnp.zeros((1, 8), jnp.int32)}
    x0 = jnp.zeros((1, 8, ycfg.latent_dim), jnp.float32)
    # compile outside the trace
    jax.block_until_ready(eng.step_slots(tok, state, act))
    jax.block_until_ready(eng.prefill_slots(toks, lens, state, act))
    jax.block_until_ready(smp.sample_from(cond, x0, 4))

    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("decode.step.k4"):
            nxt, state = eng.step_slots(tok, state, act)
        np.asarray(nxt)
    with jax.profiler.TraceAnnotation("decode.prefill.w4"):
        state = eng.prefill_slots(toks, lens, state, act)
    jax.block_until_ready(state)
    with jax.profiler.TraceAnnotation("gateway.dispatch.b4/k1"):
        np.asarray(smp.sample_from(cond, x0, 4))
    jax.profiler.stop_trace()
    out = sys.argv[1] if len(sys.argv) > 1 else HERE
    os.makedirs(out, exist_ok=True)
    src = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(src, os.path.join(out, "v5e_small.xplane.pb"))
    shutil.rmtree(d, ignore_errors=True)
    with open(os.path.join(out, "v5e_small.json"), "w") as f:
        json.dump({"device_kind": jax.devices()[0].device_kind,
                   "program_calls": {"step": 3, "prefill": 1, "flow": 1},
                   "spans": ["decode.step.k4", "decode.prefill.w4",
                             "gateway.dispatch.b4/k1"]}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
