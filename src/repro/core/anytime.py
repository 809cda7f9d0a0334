"""Anytime-BNS: ONE solver that serves multiple NFE budgets (beyond-paper).

The paper's stated limitation (Sec. 6): BNS "does need to optimize a
different solver for different NFE, which opens an interesting future
research question whether a single solver can handle different NFE without
degrading performance." This module answers it constructively.

Construction: a single NS-style solver with n = max(budgets) velocity
evaluations plus one extra OUTPUT rule (early exit) per smaller budget m:
    x_out^m = x0 * a_m + sum_{j<m} b_mj u_j .
Each exit is itself a valid NS update rule, so every truncation is a
bona-fide m-step solver. Training jointly minimizes the per-budget PSNR
losses (one Algorithm-2 run for all budgets).

Key finding (EXPERIMENTS.md §Anytime): with the paper's *monotone* time
grids, prefix-sharing is a trap — the first m eval times cannot both spread
over [0, 1] (what a dedicated m-solver needs) and precede the remaining
evals. Neither loss re-weighting nor free-but-monotone-initialized times
escape it (~23 dB below dedicated at NFE 4). The fix is a NON-MONOTONE
NESTED grid — evals 0..3 spread like a dedicated 4-grid, later evals
backfill — which nothing in Algorithm 1 forbids. With it, the shared solver
matches or beats dedicated BNS at the small budgets and gives up a few dB at
the top one.

Parameters: n(n+5)/2 + 1 + sum_{m<n}(m+1) — e.g. budgets (4,8,16): 183 vs
241 for three separate solvers, with one training run and one stored solver.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bns import BNSTrainConfig, TrainResult, psnr
from repro.core.ns_solver import NSParams
from repro.core.parametrization import VelocityField, as_partial
from repro.optim import adam_init, adam_update, cosine_annealing, poly_decay

Array = jax.Array


class AnytimeParams(NamedTuple):
    time_raw: Array   # (n,) eval times = sigmoid(time_raw) — NOT constrained
    #                   to be monotone (the nested grid is deliberately not)
    a: Array          # (n,) x0 coefficients of the intermediate update rules
    b: Array          # (n, n) velocity coefficients (row i uses j <= i)
    exit_a: Array     # (num_small,) x0 coefficient per early exit
    exit_b: Array     # (num_small, n) velocity coeffs (entries >= m unused)


def _logit(t: Array) -> Array:
    t = jnp.clip(t, 0.02, 0.98)
    return jnp.log(t / (1.0 - t))


def nested_grid(budgets: Sequence[int]) -> np.ndarray:
    """Non-monotone nested eval times: each budget's prefix spreads [0, 1)."""
    budgets = sorted(budgets)
    times: list[float] = []
    seen: set[float] = set()
    for m in budgets:
        grid = [i / m for i in range(m)]
        for t in grid:
            if t not in seen:
                seen.add(t)
                times.append(t)
    n = budgets[-1]
    assert len(times) == n, (times, budgets)
    return np.asarray(times)


def init_anytime(field: VelocityField, budgets: Sequence[int],
                 mode: str = "nested", init_solver: str = "midpoint",
                 sigma0: float = 1.0) -> AnytimeParams:
    # function-level import: repro.solvers.spec imports this module back
    from repro.solvers.registry import build_ns

    budgets = sorted(budgets)
    n = budgets[-1]
    if mode == "prefix":
        # the paper-natural (monotone, generic-solver) init — kept for the
        # ablation; it is a local-optimum trap for the small budgets.
        ns0 = build_ns(init_solver, n, field, sigma0=sigma0)
        time_raw, a, b = _logit(ns0.times), ns0.a, ns0.b
        exits_a, exits_b = [], []
        for m in budgets[:-1]:
            ns_m = build_ns(init_solver, m, field, sigma0=sigma0)
            exits_a.append(ns_m.a[-1])
            exits_b.append(jnp.pad(ns_m.b[-1], (0, n - m)))
        return AnytimeParams(time_raw=time_raw, a=a, b=b,
                             exit_a=jnp.stack(exits_a),
                             exit_b=jnp.stack(exits_b))
    assert mode == "nested", mode
    times0 = nested_grid(budgets)
    # crude Euler-from-x0 rules (x_{i+1} = x0 + t_next u_i); training refines
    a = np.ones(n)
    b = np.zeros((n, n))
    nxt = np.concatenate([times0[1:], [1.0]])
    for i in range(n):
        b[i, i] = nxt[i]
    exit_a = np.ones(len(budgets) - 1)
    exit_b = np.zeros((len(budgets) - 1, n))
    for bi, m in enumerate(budgets[:-1]):
        exit_b[bi, :m] = 1.0 / m   # Euler composition over that prefix
    return AnytimeParams(time_raw=_logit(jnp.asarray(times0)),
                         a=jnp.asarray(a), b=jnp.asarray(b),
                         exit_a=jnp.asarray(exit_a),
                         exit_b=jnp.asarray(exit_b))


class AnytimeCarry(NamedTuple):
    """Resumable state of the shared trajectory after ``step`` evaluations.

    The trajectory state after k evals is a pure function of ``x0`` and the
    recorded velocities ``U[:k]`` (every NS update rule is a weighted sum
    over them, Prop. 3.1), so this tuple is everything a later leg needs.

    x0:   the noise each row integrates from (batched leading dims).
    U:    (n, *x0.shape) recorded velocities; rows >= ``step`` are zeros.
    x:    trajectory state after ``step`` update rules.
    step: number of velocity evaluations done so far (a static Python int —
          jit carry-stepping functions per (start, stop) pair, not on it).
    rows: the rows the next leg advances (w distinct int32 indices into
          the leading axis), or None for every row; see ``anytime_extend``.
          The carry a leg returns names no rows.
    """

    x0: Array
    U: Array
    x: Array
    step: int
    rows: Array | None = None


def anytime_carry(params: AnytimeParams, budgets: Sequence[int],
                  x0: Array) -> AnytimeCarry:
    """A fresh carry at step 0 (no backbone forwards spent)."""
    n = sorted(budgets)[-1]
    return AnytimeCarry(x0=x0, U=jnp.zeros((n,) + x0.shape, x0.dtype),
                        x=x0, step=0)


def anytime_extend(params: AnytimeParams, budgets: Sequence[int],
                   u_fn: Callable, carry: AnytimeCarry, stop: int, *,
                   update_fn: Callable | None = None
                   ) -> tuple[AnytimeCarry, dict[int, Array]]:
    """Advance the shared trajectory from ``carry.step`` to ``stop`` evals,
    emitting the early-exit output of every budget crossed on the way.

    Exit-boundary join invariant (continuous batching rests on this): for
    any boundary k and served budget m in ``budgets`` with k < m, computing
    a request's prefix ``anytime_extend(fresh carry, stop=k)`` from its OWN
    noise, then extending the carry to m on the shared grid and reading the
    budget-m exit, performs bit-identically the same weighted-sum arithmetic
    as running the extracted m-step solver (``extract_ns(m)`` through
    Algorithm 1) in one go: rows 0..m-2 of the extracted solver ARE the
    shared intermediate rules, the carry after k evals is a pure function of
    (x0, U[:k]), and the zero rows of the fixed-width ``U`` buffer contribute
    exactly +0.0 to every masked weighted sum. A request admitted into an
    in-flight trajectory at boundary k therefore costs k prefix forwards
    plus the shared legs k..m — at most m forwards total, and its sample is
    the one the direct sampler would have produced.

    Costs exactly ``stop - carry.step`` velocity evaluations. ``update_fn``
    mirrors ``ns_sample(update_fn=...)`` (e.g. the Pallas ``ns_update``
    kernel); it receives the full fixed-width ``U`` with zero-masked weights.

    A carry that names ``rows`` (w distinct indices into its leading axis)
    advances those rows alone, at width w: ``u_fn`` sees only them (the
    caller gives it their conditioning), their ``U`` and ``x`` are written
    back into the full carry, the other rows keep what they held, and the
    exits are w wide (exit row j is carry row ``rows[j]``). Rows are
    independent through every update, so a row's result does not depend on
    which rows ride beside it, up to how the backend rounds a matmul at
    another width.
    """
    rows = carry.rows
    if rows is not None:
        part = AnytimeCarry(x0=carry.x0[rows], U=carry.U[:, rows],
                            x=carry.x[rows], step=carry.step)
        part, outs = anytime_extend(params, budgets, u_fn, part, stop,
                                    update_fn=update_fn)
        return AnytimeCarry(x0=carry.x0, U=carry.U.at[:, rows].set(part.U),
                            x=carry.x.at[rows].set(part.x), step=stop), outs
    budgets = sorted(budgets)
    n = budgets[-1]
    if not 0 <= carry.step < stop <= n:
        raise ValueError(f"cannot extend from step {carry.step} to {stop} "
                         f"(top budget {n})")
    if update_fn is None:
        def update_fn(x_init, U, a_i, w_i):
            return a_i * x_init + jnp.tensordot(w_i, U, axes=(0, 0))
    times = jax.nn.sigmoid(params.time_raw)
    arange = jnp.arange(n)
    x0, U, x = carry.x0, carry.U, carry.x
    outs: dict[int, Array] = {}
    for i in range(carry.step, stop):
        u = u_fn(times[i], x)
        U = jax.lax.dynamic_update_index_in_dim(U, u, i, axis=0)
        x = update_fn(x0, U, params.a[i],
                      jnp.where(arange <= i, params.b[i], 0.0))
        for bi, m in enumerate(budgets[:-1]):
            if i + 1 == m:
                outs[m] = update_fn(x0, U, params.exit_a[bi],
                                    jnp.where(arange < m, params.exit_b[bi],
                                              0.0))
    if stop == n:
        outs[n] = x
    return AnytimeCarry(x0=x0, U=U, x=x, step=stop), outs


def anytime_sample(params: AnytimeParams, budgets: Sequence[int],
                   u_fn: Callable, x0: Array, *,
                   update_fn: Callable | None = None) -> dict[int, Array]:
    """Run the shared trajectory once; emit one sample per budget.
    Stopping after m evaluations costs exactly m NFE.

    Every update (intermediate and exit) is the same weighted-sum tensordot
    Algorithm 1 uses, so each budget's output agrees with running the
    extracted m-step solver (``extract_ns``) through ``ns_solver.ns_sample``.
    ``update_fn(x0, U, a_i, w_i) -> x`` overrides that weighted sum (e.g. the
    Pallas ``ns_update`` kernel), mirroring ``ns_sample(update_fn=...)``.

    One full-length ``anytime_extend`` leg — the resumable form the
    continuous-batching engine advances boundary-by-boundary.
    """
    budgets = sorted(budgets)
    _, outs = anytime_extend(params, budgets, u_fn,
                             anytime_carry(params, budgets, x0),
                             budgets[-1], update_fn=update_fn)
    return outs


def extract_ns(params: AnytimeParams, budgets: Sequence[int],
               m: int) -> NSParams:
    """The bona-fide m-step NS solver embedded in an anytime solver.

    Rows 0..m-2 are the shared intermediate update rules; row m-1 is budget
    m's OUTPUT rule — the early exit for a small budget, or the final shared
    rule for the top one. Each exit is a valid NS rule by construction, so
    running Algorithm 1 on the result reproduces ``anytime_sample``'s output
    for that budget at exactly m NFE.
    """
    budgets = sorted(budgets)
    n = budgets[-1]
    if m not in budgets:
        raise ValueError(f"budget {m} not served; have {tuple(budgets)}")
    times = jax.nn.sigmoid(params.time_raw)[:m]
    if m == n:
        return NSParams(times=times, a=params.a, b=params.b)
    bi = budgets.index(m)
    a = jnp.concatenate([params.a[:m - 1], params.exit_a[bi][None]])
    b = jnp.concatenate([params.b[:m - 1, :m],
                         params.exit_b[bi, :m][None]], axis=0)
    return NSParams(times=times, a=a, b=b)


def train_anytime(field: VelocityField, budgets: Sequence[int], train_pairs,
                  val_pairs, cfg: BNSTrainConfig, *, mode: str = "nested",
                  weights: dict | None = None, log=None) -> TrainResult:
    """Joint Algorithm-2 optimization of the shared solver + early exits."""
    import time as _time

    budgets = sorted(budgets)
    if weights is None:
        # mild extra weight on the top budget: it owns the most parameters
        weights = {m: (2.0 if m == budgets[-1] else 1.0) for m in budgets}
    wsum = sum(weights.values())
    theta0 = init_anytime(field, budgets, mode, cfg.init_solver, cfg.sigma0)
    x0_tr, x1_tr = train_pairs
    num = x0_tr.shape[0]
    lr_fn = (poly_decay(cfg.lr, cfg.iterations) if cfg.lr_schedule == "poly"
             else cosine_annealing(cfg.lr, cfg.iterations))

    def loss_fn(theta, x0b, x1b, u):
        outs = anytime_sample(theta, budgets, u, x0b)
        total = 0.0
        for m in budgets:
            mse = jnp.mean((outs[m] - x1b) ** 2,
                           axis=tuple(range(1, x0b.ndim)))
            total = total + weights[m] * \
                jnp.mean(jnp.log(jnp.maximum(mse, 1e-20)))
        return total / wsum

    # the field rides in as an argument: its backbone weights are program
    # inputs, never constants baked into the step
    u = as_partial(field.fn)

    @jax.jit
    def step(theta, opt, it, x0b, x1b, u):
        loss, grads = jax.value_and_grad(loss_fn)(theta, x0b, x1b, u)
        theta, opt = adam_update(grads, opt, theta, lr_fn(it))
        return theta, opt, loss

    @jax.jit
    def val_psnr(theta, u):
        outs = anytime_sample(theta, budgets, u, val_pairs[0])
        return jnp.mean(jnp.stack(
            [jnp.mean(psnr(outs[m], val_pairs[1], cfg.max_val))
             for m in budgets]))

    theta, opt = theta0, adam_init(theta0)
    rng = np.random.default_rng(cfg.seed)
    best = (-np.inf, theta)
    history = []
    t0 = _time.time()
    for it in range(cfg.iterations):
        idx = (np.arange(num) if cfg.batch_size >= num
               else rng.choice(num, size=cfg.batch_size, replace=False))
        theta, opt, loss = step(theta, opt, jnp.asarray(it), x0_tr[idx],
                                x1_tr[idx], u)
        if (it + 1) % cfg.val_every == 0 or it == cfg.iterations - 1:
            vp = float(val_psnr(theta, u))
            history.append((it + 1, float(loss), vp))
            if vp > best[0]:
                best = (vp, jax.tree.map(lambda x: x.copy(), theta))
            if log:
                log(f"anytime iter {it+1}: loss={float(loss):.3f} "
                    f"mean_psnr={vp:.2f}dB")
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(best[1]))
    return TrainResult(params=best[1], val_psnr=best[0], history=history,
                       wall_seconds=_time.time() - t0, nfe=budgets[-1],
                       num_parameters=n_params)


def evaluate_anytime(params: AnytimeParams, budgets: Sequence[int],
                     field: VelocityField, pairs, max_val: float = 1.0
                     ) -> dict[int, float]:
    x0, x1 = pairs
    outs = anytime_sample(params, sorted(budgets), field.fn, x0)
    return {m: float(jnp.mean(psnr(outs[m], x1, max_val))) for m in outs}
