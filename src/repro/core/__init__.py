"""repro.core — Bespoke Non-Stationary solvers (Shaul et al., ICML 2024).

The paper's math lives here; the solver *product* API lives in
``repro.solvers`` (registry / SolverSpec / SolverArtifact / Sampler):

    from repro.solvers import SolverSpec
    spec = SolverSpec("midpoint", nfe=8, mode="bns")
    art = spec.distill(field, train_pairs, val_pairs, cfg).artifact()
    art.save("solver.msgpack")     # serve without retraining

Public API (this package):
  schedulers:      fm_ot, fm_cs, vp, ve, scaled_sigma, get_scheduler
  parametrization: as_velocity_field (velocity / eps-pred / x-pred)
  solvers:         generic solver programs + grids (the taxonomy inputs)
  exponential:     ddim / dpm2m programs + the log-SNR grid
  st_transform/st_solvers: scheduler_change_st, preconditioning, EDM
  ns_solver:       NSParams / BNSParams, ns_sample (Algorithm 1)
  taxonomy:        to_ns / run_direct (Theorem 3.2, executable)
  bns:             generate_pairs, train_bns / train_bst (Algorithm 2);
                   ``solver_to_ns`` survives only as a deprecation shim over
                   ``repro.solvers.registry.build_ns``
  anytime:         one shared solver for multiple NFE budgets (beyond-paper)
"""
from repro.core import (
    anytime,
    bns,
    bst_solver,
    exponential,
    ns_solver,
    parametrization,
    rk45,
    schedulers,
    solvers,
    st_solvers,
    st_transform,
    taxonomy,
    toy,
)

__all__ = [
    "anytime", "bns", "bst_solver", "exponential", "ns_solver", "parametrization",
    "rk45", "schedulers", "solvers", "st_solvers", "st_transform", "taxonomy",
    "toy",
]
