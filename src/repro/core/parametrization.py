"""Model-output parametrizations and their conversion to velocity fields.

Table 1 of the paper: the sampling velocity is
    u_t(x) = beta_t * x + gamma_t * f_t(x)
with (beta, gamma) depending on whether f is a velocity, epsilon-prediction,
or x-prediction model.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
from jax.tree_util import Partial

from repro.core.schedulers import Scheduler

Array = jax.Array
# f(t, x) -> prediction; conditioning is closed over by the caller.
ModelFn = Callable[[Array, Array], Array]

VELOCITY = "velocity"
EPS_PRED = "eps"
X_PRED = "x"

PARAMETRIZATIONS = (VELOCITY, EPS_PRED, X_PRED)


def beta_gamma(sched: Scheduler, parametrization: str, t: Array):
    """Coefficients of Table 1 for ``u = beta x + gamma f``."""
    if parametrization == VELOCITY:
        return jnp.zeros_like(t), jnp.ones_like(t)
    a, s = sched.alpha(t), sched.sigma(t)
    da, ds = sched.dalpha(t), sched.dsigma(t)
    if parametrization == EPS_PRED:
        return da / a, (ds * a - s * da) / a
    if parametrization == X_PRED:
        return ds / s, (s * da - ds * a) / s
    raise ValueError(f"unknown parametrization {parametrization!r}")


@dataclasses.dataclass(frozen=True)
class VelocityField:
    """A sampling-ready velocity field u_t(x) built from a model f.

    ``fn(t, x)`` evaluates u; ``scheduler`` is the Gaussian-path scheduler the
    model was trained with (needed by ST transforms and dedicated solvers).
    """

    fn: ModelFn
    scheduler: Scheduler

    def __call__(self, t: Array, x: Array) -> Array:
        return self.fn(t, x)


def as_partial(fn: Callable) -> Partial:
    """``fn`` as a pytree that a jit can take as an argument. A
    ``Partial`` (e.g. the backbone's ``models.model.velocity_field``)
    passes through, so its arrays reach the program as inputs; any other
    callable becomes a leafless ``Partial`` and stays static."""
    return fn if isinstance(fn, Partial) else Partial(fn)


def as_velocity_field(
    model: ModelFn, sched: Scheduler, parametrization: str = VELOCITY
) -> VelocityField:
    """Wrap an f-model (velocity / eps-pred / x-pred) into u_t(x) (Table 1)."""
    if parametrization == VELOCITY:
        return VelocityField(fn=model, scheduler=sched)

    def u(t: Array, x: Array) -> Array:
        t = sched.clip_t(t)
        beta, gamma = beta_gamma(sched, parametrization, t)
        return beta * x + gamma * model(t, x)

    return VelocityField(fn=u, scheduler=sched)


def eps_to_velocity(sched: Scheduler, t: Array, x: Array, eps: Array) -> Array:
    beta, gamma = beta_gamma(sched, EPS_PRED, t)
    return beta * x + gamma * eps


def x_to_velocity(sched: Scheduler, t: Array, x: Array, x1: Array) -> Array:
    beta, gamma = beta_gamma(sched, X_PRED, t)
    return beta * x + gamma * x1
