"""BNS solver training — Algorithm 2 of the paper.

Pipeline:
  1. generate (x0, x(1)) pairs with adaptive RK45 from the frozen model;
  2. initialize theta from a generic solver (optionally sigma0-preconditioned
     via a Scale-Time scheduler change, eq. 14) converted to NS parameters;
  3. minimize the PSNR loss  L(theta) = E log ||x_n^theta - x(1)||^2  with
     Adam, tracking PSNR on a validation set and returning the best iterate.

The same harness trains BST solvers (the prior-work baseline) by swapping the
sampler.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.tree_util import Partial

from repro.core import bst_solver, ns_solver, st_transform
from repro.core.ns_solver import BNSParams, NSParams
from repro.core.parametrization import VelocityField, as_partial
from repro.core.rk45 import rk45_solve
from repro.core.taxonomy import run_direct
from repro.optim import adam_init, adam_update, cosine_annealing, poly_decay

Array = jax.Array


# ---------------------------------------------------------------------------
# Ground-truth pair generation
# ---------------------------------------------------------------------------


def generate_pairs(
    field: VelocityField,
    key: Array,
    num: int,
    shape: tuple[int, ...],
    *,
    batch_size: int = 64,
    rtol: float = 1e-5,
    atol: float = 1e-5,
    source_std: float = 1.0,
) -> tuple[Array, Array]:
    """Draw x0 ~ N(0, source_std^2) and integrate to x(1) with RK45."""
    solve = jax.jit(lambda u, x0: rk45_solve(u, x0, rtol=rtol, atol=atol).x1)
    u = as_partial(field.fn)
    x0s, x1s = [], []
    for start in range(0, num, batch_size):
        b = min(batch_size, num - start)
        key, sub = jax.random.split(key)
        x0 = source_std * jax.random.normal(sub, (b,) + shape)
        x0s.append(x0)
        x1s.append(solve(u, x0))
    return jnp.concatenate(x0s), jnp.concatenate(x1s)


# ---------------------------------------------------------------------------
# Initialization (generic solver -> NS params, with preconditioning)
# ---------------------------------------------------------------------------


def solver_to_ns(
    name: str,
    nfe: int,
    field: VelocityField,
    *,
    sigma0: float = 1.0,
    grid=None,
) -> NSParams:
    """DEPRECATED shim over ``repro.solvers.registry.build_ns``.

    The string-dispatch ladder that used to live here is now the solver
    registry; use ``repro.solvers.build_ns`` (or ``SolverSpec.build``)
    directly. Kept so existing call sites and tests keep working.
    """
    import warnings

    from repro.solvers.registry import build_ns

    warnings.warn("solver_to_ns is deprecated; use repro.solvers.build_ns "
                  "or SolverSpec.build", DeprecationWarning, stacklevel=2)
    return build_ns(name, nfe, field, sigma0=sigma0, grid=grid)


def _ns_sample(u_fn, theta: BNSParams, x0: Array) -> Array:
    return ns_solver.ns_sample(ns_solver.materialize(theta), u_fn, x0)


def ns_sampler(field: VelocityField) -> Callable[[BNSParams, Array], Array]:
    """``sample(theta, x0)`` as a ``Partial`` over the field's ``fn``, so a
    jit taking the sampler as an argument gets the backbone as inputs."""
    return Partial(_ns_sample, as_partial(field.fn))


def bst_sampler(field: VelocityField, base: str = "euler"):
    prog = (bst_solver.bst_euler_program if base == "euler"
            else bst_solver.bst_midpoint_program)

    def sample(theta: bst_solver.BSTParams, x0: Array) -> Array:
        return run_direct(prog, field, x0, bst_solver.materialize_bst(theta))

    return sample


# ---------------------------------------------------------------------------
# Algorithm 2
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class BNSTrainConfig:
    nfe: int = 8
    init_solver: str = "midpoint"
    sigma0: float = 1.0
    lr: float = 5e-4
    lr_schedule: str = "poly"        # "poly" | "cosine" (paper: poly for class-cond, cosine for T2I/audio)
    iterations: int = 2000
    batch_size: int = 40
    val_every: int = 100
    seed: int = 0
    max_val: float = 1.0             # PSNR peak value (1.0 for unit-scale latents)


@dataclasses.dataclass
class TrainResult:
    params: object                   # best-validation parameters
    val_psnr: float
    history: list                    # (iter, train_loss, val_psnr)
    wall_seconds: float
    nfe: int
    num_parameters: int


def psnr(x: Array, ref: Array, max_val: float = 1.0) -> Array:
    """Per-pair PSNR with the paper's norm ||x||^2 = mean_i x_i^2."""
    mse = jnp.mean((x - ref) ** 2, axis=tuple(range(1, x.ndim)))
    return 10.0 * (2.0 * jnp.log10(max_val) - jnp.log10(jnp.maximum(mse, 1e-20)))


def _loss_fn(sampler, theta, x0, x1):
    xh = sampler(theta, x0)
    mse = jnp.mean((xh - x1) ** 2, axis=tuple(range(1, x0.ndim)))
    return jnp.mean(jnp.log(jnp.maximum(mse, 1e-20)))


def train_solver(
    sampler: Callable,
    theta0,
    train_pairs: tuple[Array, Array],
    val_pairs: tuple[Array, Array],
    cfg: BNSTrainConfig,
    *,
    log: Callable[[str], None] | None = None,
) -> TrainResult:
    """Generic Algorithm-2 optimizer over any differentiable sampler."""
    x0_tr, x1_tr = train_pairs
    num = x0_tr.shape[0]
    lr_fn = (poly_decay(cfg.lr, cfg.iterations) if cfg.lr_schedule == "poly"
             else cosine_annealing(cfg.lr, cfg.iterations))

    # the sampler rides in as an argument: its backbone weights are program
    # inputs, never constants baked into the step
    sampler = as_partial(sampler)

    @jax.jit
    def step(theta, opt, it, x0b, x1b, sampler):
        loss, grads = jax.value_and_grad(
            lambda th: _loss_fn(sampler, th, x0b, x1b))(theta)
        theta, opt = adam_update(grads, opt, theta, lr_fn(it))
        return theta, opt, loss

    @jax.jit
    def val_psnr_fn(theta, sampler):
        return jnp.mean(psnr(sampler(theta, val_pairs[0]), val_pairs[1],
                             cfg.max_val))

    theta, opt = theta0, adam_init(theta0)
    rng = np.random.default_rng(cfg.seed)
    best = (-np.inf, theta)
    history = []
    t_start = time.time()
    full_batch = cfg.batch_size >= num
    for it in range(cfg.iterations):
        # conditional fields close over a fixed conditioning batch: row i of
        # the pairs is tied to conditioning row i, so full-batch runs must
        # keep the order (no shuffling).
        idx = np.arange(num) if full_batch else \
            rng.choice(num, size=cfg.batch_size, replace=False)
        theta, opt, loss = step(theta, opt, jnp.asarray(it), x0_tr[idx],
                                x1_tr[idx], sampler)
        if (it + 1) % cfg.val_every == 0 or it == cfg.iterations - 1:
            vp = float(val_psnr_fn(theta, sampler))
            history.append((it + 1, float(loss), vp))
            if vp > best[0]:
                best = (vp, jax.tree.map(lambda x: x.copy(), theta))
            if log:
                log(f"iter {it+1}: loss={float(loss):.4f} val_psnr={vp:.2f}dB")
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(best[1]))
    return TrainResult(params=best[1], val_psnr=best[0], history=history,
                       wall_seconds=time.time() - t_start, nfe=cfg.nfe,
                       num_parameters=n_params)


def train_bns(
    field: VelocityField,
    train_pairs,
    val_pairs,
    cfg: BNSTrainConfig,
    *,
    log=None,
) -> TrainResult:
    from repro.solvers.registry import build_ns

    ns0 = build_ns(cfg.init_solver, cfg.nfe, field, sigma0=cfg.sigma0)
    theta0 = ns_solver.from_ns(ns0)
    res = train_solver(ns_sampler(field), theta0, train_pairs, val_pairs, cfg, log=log)
    # Report the paper's parameter count (canonical dimension of the family).
    res.num_parameters = ns_solver.count_parameters(cfg.nfe)
    return res


def make_distributed_bns_step(field: VelocityField, cfg: BNSTrainConfig, mesh):
    """pjit'd Algorithm-2 step for the production mesh.

    BNS training is embarrassingly data-parallel: the (x0, x1) pairs shard
    over the composed batch axes, theta (<200 floats) and the Adam state are
    replicated, and the per-device gradients all-reduce. The backbone params
    inside ``field`` shard via their own closure-captured shardings.
    Returns (step_fn, theta0, opt0); step_fn(theta, opt, it, x0b, x1b).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import batch_axes
    from repro.solvers.registry import build_ns

    b = batch_axes(mesh)
    b = b if len(b) > 1 else b[0]
    sampler = ns_sampler(field)
    lr_fn = (poly_decay(cfg.lr, cfg.iterations) if cfg.lr_schedule == "poly"
             else cosine_annealing(cfg.lr, cfg.iterations))

    def step(theta, opt, it, x0b, x1b):
        loss, grads = jax.value_and_grad(
            lambda th: _loss_fn(sampler, th, x0b, x1b))(theta)
        theta, opt = adam_update(grads, opt, theta, lr_fn(it))
        return theta, opt, loss

    ns0 = build_ns(cfg.init_solver, cfg.nfe, field, sigma0=cfg.sigma0)
    theta0 = ns_solver.from_ns(ns0)
    opt0 = adam_init(theta0)
    repl = NamedSharding(mesh, P())
    pair_sharding = NamedSharding(mesh, P(b))
    step_fn = jax.jit(
        step,
        in_shardings=(jax.tree.map(lambda _: repl, theta0),
                      jax.tree.map(lambda _: repl, opt0),
                      repl, pair_sharding, pair_sharding),
        out_shardings=(jax.tree.map(lambda _: repl, theta0),
                       jax.tree.map(lambda _: repl, opt0), repl))
    return step_fn, theta0, opt0


def train_bst(
    field: VelocityField,
    train_pairs,
    val_pairs,
    cfg: BNSTrainConfig,
    *,
    base: str = "euler",
    log=None,
) -> TrainResult:
    if cfg.sigma0 != 1.0:
        target = st_transform.scaled_sigma(field.scheduler, cfg.sigma0)
        st = st_transform.scheduler_change_st(field.scheduler, target)
        theta0 = bst_solver.from_st_transform(st, cfg.nfe, base)
    else:
        theta0 = bst_solver.identity_bst(cfg.nfe, base)
    return train_solver(bst_sampler(field, base), theta0, train_pairs, val_pairs,
                        cfg, log=log)
