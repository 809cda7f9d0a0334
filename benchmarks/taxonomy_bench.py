"""Figure 3 / Theorem 3.2 benchmark: every solver family converts to NS
parameters with numerically-exact trajectory agreement.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import ns_solver, schedulers, solvers, st_solvers, st_transform, taxonomy, toy
from repro.core.bst_solver import bst_euler_program, identity_bst, materialize_bst
from repro.core.exponential import ddim_program, dpm2m_program
from repro.solvers import build_ns, get_solver


def run(log=print):
    sched = schedulers.fm_ot()
    field = toy.mixture_field(sched, toy.two_moons_means(),
                              jnp.full((16,), 0.15), jnp.ones((16,)))
    x0 = jax.random.normal(jax.random.PRNGKey(0), (64, 2))
    rows = []

    # registered solvers: direct program run vs the registry's NS build
    cases = []
    for name in ["euler", "midpoint", "heun", "rk4", "ab2", "ab4"]:
        grid = get_solver(name).default_grid(8, field)
        cases.append((name, solvers.solver_program(name), (grid,),
                      build_ns(name, 8, field)))
    for name, prog in [("ddim", ddim_program), ("dpm2m", dpm2m_program)]:
        cases.append((name, prog, (get_solver(name).default_grid(8, field),
                                   sched), build_ns(name, 8, field)))
    # bespoke constructions outside the registry: convert via taxonomy directly
    st = st_transform.scheduler_change_st(sched, st_transform.scaled_sigma(sched, 3.0))
    cases.append(("st_euler_sigma3", st_solvers.st_program(solvers.euler_program, st),
                  (solvers.uniform_grid(8),), None))
    cases.append(("edm_heun", st_solvers.edm_program(solvers.heun_program, sched, 20.0),
                  (solvers.power_grid(4, 3.0),), None))
    cases.append(("bst_euler", bst_euler_program,
                  (materialize_bst(identity_bst(8)),), None))

    for name, prog, args, ns in cases:
        direct = taxonomy.run_direct(prog, field, x0, *args)
        if ns is None:
            ns = taxonomy.to_ns(prog, *args)
        sample = jax.jit(lambda x, p=ns: ns_solver.ns_sample(p, field.fn, x))
        out = sample(x0)
        err = float(jnp.max(jnp.abs(out - direct)))
        rows.append({"solver": name, "n": ns.n, "max_err": err})
        log(f"[{'PASS' if err < 1e-3 else 'FAIL'}] {name:16s} -> NS(n={ns.n}) "
            f"max|direct - Alg.1| = {err:.2e}")
    return rows


if __name__ == "__main__":
    run()
