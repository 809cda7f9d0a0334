"""Table 2 / Table 5 reproduction (proxy scale): conditional generation with
CFG on a REAL backbone from the assigned pool.

Pipeline (the paper's, end to end):
  1. train a flow-matching model (yi-6b smoke backbone) on the synthetic
     token stream (launch.train);
  2. generate RK45 ground-truth latents for held-out conditioning, under
     classifier-free guidance w;
  3. evaluate RK-Euler / RK-Midpoint baselines at each NFE;
  4. train BNS solvers (with sigma0 preconditioning at high w, as the paper
     prescribes) and compare PSNR;
  5. Table 5 ablation: BNS vs its own initialization solver.
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

ARCH = "yi-6b"
SEQ, BATCH = 16, 32
NFES = [8, 12]
BASELINES = solver_names(family="generic", baseline=True)  # euler, midpoint


def build_field(params, cfg, batch, w):
    return M.velocity_field(params, cfg, fm_ot(), batch, cfg_scale=w)


def make_pairs(field, key, num, latent_dim):
    x0 = jax.random.normal(key, (num, SEQ, latent_dim))
    x1 = jax.jit(lambda x: rk45_solve(field.fn, x, rtol=1e-5, atol=1e-5).x1)(x0)
    return x0, x1


def run(w: float = 2.0, train_steps: int = 250, bns_iters: int = 400,
        log=print) -> list[dict]:
    cfg = get_config(ARCH, smoke=True)
    params, losses = train(ARCH, smoke=True, steps=train_steps, batch=16,
                           seq=SEQ, lr=1e-3, log=lambda *_: None)
    log(f"backbone CFM loss: {losses[0]:.3f} -> {losses[-1]:.3f}")

    data = SyntheticTokens(cfg, DataConfig(batch_size=BATCH, seq_len=SEQ,
                                           seed=99))
    cond = data.batch(0)
    field = build_field(params, cfg, cond, w)
    train_pairs = make_pairs(field, jax.random.PRNGKey(10), BATCH,
                             cfg.latent_dim)
    val_pairs = make_pairs(field, jax.random.PRNGKey(11), BATCH,
                           cfg.latent_dim)

    rows = []
    for nfe in NFES:
        row = {"w": w, "nfe": nfe}
        for name in BASELINES:
            row[name] = SolverSpec(name, nfe).sampler(field).psnr(val_pairs)
        # initial solver = preconditioned Euler (Table 5's 'Initial Solver')
        sigma0 = 1.0 if w == 0.0 else 2.0
        spec = SolverSpec("euler", nfe, sigma0=sigma0, cfg_scale=w, mode="bns")
        row["initial_solver"] = spec.sampler(field).psnr(val_pairs)
        cfg_bns = BNSTrainConfig(lr=1e-3, lr_schedule="cosine",
                                 iterations=bns_iters, val_every=50,
                                 batch_size=BATCH)
        row["bns"] = spec.distill(field, train_pairs, val_pairs,
                                  cfg_bns).val_psnr
        rows.append(row)
        log(f"w={w} NFE={nfe}: euler={row['euler']:.2f} "
            f"midpoint={row['midpoint']:.2f} init={row['initial_solver']:.2f} "
            f"BNS={row['bns']:.2f}")
    return rows


def check_paper_claims(rows, log=print):
    notes = []
    for r in rows:
        ok = r["bns"] > max(r["euler"], r["midpoint"], r["initial_solver"])
        notes.append(f"[{'PASS' if ok else 'FAIL'}] w={r['w']} NFE={r['nfe']}: "
                     f"BNS beats RK baselines and its own init "
                     f"(Table 2 + Table 5 pattern)")
    return notes


if __name__ == "__main__":
    rows = run()
    for n in check_paper_claims(rows):
        print(n)
