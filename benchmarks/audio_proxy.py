"""Figure 6 / Sec 5.4 reproduction (proxy scale): SNR vs NFE for the
enc-dec audio backbone (whisper-medium smoke), conditioned on stub frame
embeddings — the paper's speech-infill setting with Encodec features swapped
for our latent sequences.

Expected: BNS SNR above every baseline at each NFE (paper: +1-3 dB over
runner-up across all 8 datasets).
"""
from __future__ import annotations

import jax

from repro.configs import get_config
from repro.core.bns import BNSTrainConfig
from repro.core.rk45 import rk45_solve
from repro.core.schedulers import fm_ot
from repro.data.synthetic import DataConfig, SyntheticTokens
from repro.launch.train import train
from repro.models import model as M
from repro.solvers import SolverSpec, solver_names

ARCH = "whisper-medium"
SEQ, BATCH = 16, 24
NFES = [8, 16]
BASELINES = solver_names(family="generic", baseline=True)  # euler, midpoint


def run(train_steps: int = 200, bns_iters: int = 300, log=print):
    cfg = get_config(ARCH, smoke=True)
    params, losses = train(ARCH, smoke=True, steps=train_steps, batch=8,
                           seq=SEQ, lr=1e-3, log=lambda *_: None)
    log(f"audio backbone CFM loss: {losses[0]:.3f} -> {losses[-1]:.3f}")
    data = SyntheticTokens(cfg, DataConfig(batch_size=BATCH, seq_len=SEQ,
                                           seed=7))
    cond = data.batch(0)
    field = M.velocity_field(params, cfg, fm_ot(), cond, cfg_scale=0.0)
    x0 = jax.random.normal(jax.random.PRNGKey(3), (BATCH, SEQ, cfg.latent_dim))
    x1 = jax.jit(lambda x: rk45_solve(field.fn, x, rtol=1e-5, atol=1e-5).x1)(x0)
    x0v = jax.random.normal(jax.random.PRNGKey(4), (BATCH, SEQ, cfg.latent_dim))
    x1v = jax.jit(lambda x: rk45_solve(field.fn, x, rtol=1e-5, atol=1e-5).x1)(x0v)

    rows = []
    for nfe in NFES:
        row = {"nfe": nfe}
        for name in BASELINES:
            # SNR(dB) wrt RK45 ground truth == PSNR with max_val = rms(signal)
            row[name] = SolverSpec(name, nfe).sampler(field).psnr((x0v, x1v))
        cfg_bns = BNSTrainConfig(lr=1e-3, lr_schedule="cosine",
                                 iterations=bns_iters, val_every=50,
                                 batch_size=BATCH)
        row["bns"] = SolverSpec("midpoint", nfe, mode="bns") \
            .distill(field, (x0, x1), (x0v, x1v), cfg_bns).val_psnr
        rows.append(row)
        log(f"audio NFE={nfe}: euler={row['euler']:.2f} "
            f"midpoint={row['midpoint']:.2f} BNS={row['bns']:.2f}")
    return rows


def check_paper_claims(rows):
    return [f"[{'PASS' if r['bns'] > max(r['euler'], r['midpoint']) else 'FAIL'}]"
            f" audio NFE={r['nfe']}: BNS above runner-up (Fig 6 pattern)"
            for r in rows]


if __name__ == "__main__":
    for n in check_paper_claims(run()):
        print(n)
