"""Figure 4 / Table 4 reproduction (analytic-teacher scale): PSNR vs NFE for
BNS against BST and every baseline solver family, across the paper's three
pre-trained-model types (FM-OT, FM/v-CS, eps-VP schedulers).

Expected (paper): BNS > BST > DPM > RK-Midpoint/Euler in PSNR at low NFE, and
PSNR monotone in NFE. The 'pre-trained model' here is the closed-form
Gaussian-mixture velocity field (exact marginal flow) — solver behaviour, not
network capacity, is what this figure measures.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import schedulers, toy
from repro.core.bns import BNSTrainConfig, generate_pairs
from repro.solvers import SolverSpec, solver_names

SCHEDS = ["fm_ot", "fm_cs", "vp"]
NFES = [4, 8, 16]
BASELINES = solver_names(baseline=True)  # euler, midpoint, ddim, dpm2m


def make_field(sname: str):
    sched = schedulers.get_scheduler(sname)
    return toy.mixture_field(sched, toy.two_moons_means(),
                             jnp.full((16,), 0.15), jnp.ones((16,)))


def run(iterations: int = 3000, lr: float = 1e-3, log=print) -> list[dict]:
    rows = []
    for sname in SCHEDS:
        field = make_field(sname)
        train = generate_pairs(field, jax.random.PRNGKey(0), 256, (2,))
        val = generate_pairs(field, jax.random.PRNGKey(1), 256, (2,))
        for nfe in NFES:
            row = {"scheduler": sname, "nfe": nfe}
            for name in BASELINES:
                row[name] = SolverSpec(name, nfe).sampler(field).psnr(val)
            cfg = BNSTrainConfig(lr=lr, iterations=iterations, val_every=100,
                                 batch_size=64)
            row["bns"] = SolverSpec("midpoint", nfe, mode="bns") \
                .distill(field, train, val, cfg).val_psnr
            row["bst"] = SolverSpec("euler", nfe, mode="bst") \
                .distill(field, train, val, cfg).val_psnr
            rows.append(row)
            log(f"{sname} NFE={nfe}: " + " ".join(
                f"{k}={v:.2f}" for k, v in row.items()
                if isinstance(v, float)))
    return rows


def check_paper_claims(rows: list[dict]) -> list[str]:
    """Validate the orderings the paper reports (Fig 4, Fig 11)."""
    notes = []
    for r in rows:
        runner_up = max(r[b] for b in BASELINES + ["bst"])
        # Paper Sec. 6: BNS "doesn't reach the extremely low NFE regime
        # (1-4)" — at NFE 4 we require parity with the trained-BST runner-up
        # (within 2 dB); at NFE >= 8 BNS must win outright.
        margin = 2.0 if r["nfe"] <= 4 else 0.0
        ok = r["bns"] > runner_up - margin
        notes.append(
            f"[{'PASS' if ok else 'FAIL'}] {r['scheduler']} NFE={r['nfe']}: "
            f"BNS {r['bns']:.2f} vs best-other {runner_up:.2f}"
            + (" (NFE<=4 parity band, paper Sec. 6 caveat)" if margin else ""))
        ok_bst = r["bst"] >= r["euler"] - 0.2
        notes.append(
            f"[{'PASS' if ok_bst else 'FAIL'}] {r['scheduler']} NFE={r['nfe']}: "
            f"BST {r['bst']:.2f} >= Euler {r['euler']:.2f} (trained >= init)")
    for sname in SCHEDS:
        per = [r["bns"] for r in rows if r["scheduler"] == sname]
        mono = all(b > a for a, b in zip(per, per[1:]))
        notes.append(f"[{'PASS' if mono else 'FAIL'}] {sname}: BNS PSNR "
                     f"monotone in NFE {['%.1f' % p for p in per]}")
    return notes


if __name__ == "__main__":
    rows = run()
    for n in check_paper_claims(rows):
        print(n)
