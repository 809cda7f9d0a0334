"""Beyond-paper benchmark: Anytime-BNS (the paper's Sec. 6 open question —
can a single solver serve multiple NFE budgets?).

Compares one jointly-trained solver with non-monotone nested grid against
(i) dedicated per-NFE BNS solvers and (ii) the untrained generic baseline,
at budgets {4, 8, 16} on the FM-OT analytic teacher.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import schedulers, toy
from repro.core.anytime import evaluate_anytime
from repro.core.bns import BNSTrainConfig, generate_pairs
from repro.solvers import SolverSpec

BUDGETS = (4, 8, 16)


def run(iterations: int = 10_000, dedicated_iters: int = 3000, log=print):
    sched = schedulers.fm_ot()
    field = toy.mixture_field(sched, toy.two_moons_means(),
                              jnp.full((16,), 0.15), jnp.ones((16,)))
    train = generate_pairs(field, jax.random.PRNGKey(0), 256, (2,))
    val = generate_pairs(field, jax.random.PRNGKey(1), 256, (2,))

    cfg = BNSTrainConfig(iterations=iterations, lr=1.5e-3, val_every=500,
                         batch_size=64)
    res = SolverSpec("midpoint", mode="anytime", budgets=BUDGETS) \
        .distill(field, train, val, cfg)
    anytime_scores = evaluate_anytime(res.params, BUDGETS, field, val)

    rows = []
    for m in BUDGETS:
        ded = SolverSpec("midpoint", m, mode="bns").distill(
            field, train, val,
            BNSTrainConfig(iterations=dedicated_iters, lr=1e-3,
                           val_every=300, batch_size=64))
        bp = SolverSpec("midpoint", m).sampler(field).psnr(val)
        rows.append({"nfe": m, "anytime": anytime_scores[m],
                     "dedicated": ded.val_psnr, "midpoint": bp})
        log(f"anytime NFE={m}: shared={anytime_scores[m]:.2f} "
            f"dedicated={ded.val_psnr:.2f} midpoint={bp:.2f} "
            f"(shared solver: {res.num_parameters} params total)")
    return rows, res.num_parameters


def check_claims(rows):
    notes = []
    for r in rows:
        ok = r["anytime"] > r["midpoint"]
        notes.append(f"[{'PASS' if ok else 'FAIL'}] anytime NFE={r['nfe']}: "
                     f"shared solver beats the generic baseline")
    return notes


def serve_bench(iterations: int = 600, log=print):
    """Anytime SERVING: one zoo-cached artifact serves every budget through
    its extracted m-step solver. The second zoo ``get`` is a memory hit
    (zero distillation). Returns csv-ready rows: each extracted solver's
    PSNR."""
    from repro.serving import SolverZoo
    from repro.solvers import Sampler

    sched = schedulers.fm_ot()
    field = toy.mixture_field(sched, toy.two_moons_means(),
                              jnp.full((16,), 0.15), jnp.ones((16,)))
    train = generate_pairs(field, jax.random.PRNGKey(0), 128, (2,))
    val = generate_pairs(field, jax.random.PRNGKey(1), 128, (2,))
    spec = SolverSpec("midpoint", mode="anytime", budgets=BUDGETS)
    cfg = BNSTrainConfig(iterations=iterations, lr=1.5e-3, val_every=200,
                         batch_size=64)

    zoo = SolverZoo(capacity=4)
    art = zoo.get(spec, field=field, train_pairs=train, val_pairs=val,
                  train_cfg=cfg)
    assert zoo.get(spec) is art
    assert zoo.stats.distills == 1 and zoo.stats.hits == 1
    log("zoo: the second get is a cache hit — one distillation serves "
        "every budget")
    rows = []
    for m in BUDGETS:
        psnr = Sampler(art.ns_at_budget(m), field).psnr(val)
        log(f"serve NFE={m}: extracted {m}-step solver PSNR {psnr:.2f}")
        rows.append({"name": f"nfe{m}", "derived": f"psnr={psnr:.2f}"})
    return rows


if __name__ == "__main__":
    rows, _ = run()
    for n in check_claims(rows):
        print(n)
    for r in serve_bench():
        print(f"anytime_serving/{r['name']},{r['derived']}")
