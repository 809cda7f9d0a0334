"""Adaptive Dormand-Prince RK45 (Shampine 1986) — the ground-truth sampler.

The paper generates its BNS training/validation pairs (x0, x(1)) with
adaptive RK45 and reports PSNR against them. Implemented with
``lax.while_loop`` so GT generation is jit-able and batchable; step-size
control is the standard PI-free accept/reject with error order 5.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

# Dormand-Prince Butcher tableau (DOPRI5). NumPy constants, so importing
# this module never starts a JAX backend.
_C = np.array([0.0, 1 / 5, 3 / 10, 4 / 5, 8 / 9, 1.0, 1.0], np.float32)
_A = np.array([
    [0, 0, 0, 0, 0, 0],
    [1 / 5, 0, 0, 0, 0, 0],
    [3 / 40, 9 / 40, 0, 0, 0, 0],
    [44 / 45, -56 / 15, 32 / 9, 0, 0, 0],
    [19372 / 6561, -25360 / 2187, 64448 / 6561, -212 / 729, 0, 0],
    [9017 / 3168, -355 / 33, 46732 / 5247, 49 / 176, -5103 / 18656, 0],
], np.float32)
_B5 = np.array([35 / 384, 0.0, 500 / 1113, 125 / 192, -2187 / 6784, 11 / 84, 0.0],
               np.float32)
_B4 = np.array([5179 / 57600, 0.0, 7571 / 16695, 393 / 640,
                 -92097 / 339200, 187 / 2100, 1 / 40], np.float32)


class RK45Result(NamedTuple):
    x1: Array
    nfe: Array
    accepted: Array
    rejected: Array


def rk45_solve(
    u_fn: Callable[[Array, Array], Array],
    x0: Array,
    *,
    t0: float = 0.0,
    t1: float = 1.0,
    rtol: float = 1e-5,
    atol: float = 1e-5,
    h0: float = 0.01,
    max_steps: int = 10_000,
) -> RK45Result:
    """Integrate dx/dt = u(t, x) from t0 to t1 adaptively.

    ``u_fn`` must accept a scalar t and a full (batched) state; error control
    uses the max norm over the whole state so every batch element meets tol
    (conservative — matches 'high-accuracy GT' use).
    """

    def rk_step(t, x, h):
        ks = []
        for i in range(7):
            if i == 0:
                xi = x
            else:
                acc = ks[0] * _A[i - 1, 0]
                for j in range(1, i):
                    acc = acc + ks[j] * _A[i - 1, j]
                xi = x + h * acc
            if i < 6:
                ks.append(u_fn(t + h * _C[i], xi))
            else:
                # FSAL stage evaluated at t+h with 5th-order solution.
                x5 = x + h * sum(ks[j] * _B5[j] for j in range(6))
                ks.append(u_fn(t + h, x5))
        x5 = x + h * sum(ks[j] * _B5[j] for j in range(7))
        x4 = x + h * sum(ks[j] * _B4[j] for j in range(7))
        return x5, x4

    def cond(state):
        t, x, h, nfe, acc, rej, steps = state
        return (t < t1 - 1e-12) & (steps < max_steps)

    def body(state):
        t, x, h, nfe, acc, rej, steps = state
        h = jnp.minimum(h, t1 - t)
        x5, x4 = rk_step(t, x, h)
        scale = atol + rtol * jnp.maximum(jnp.abs(x), jnp.abs(x5))
        err = jnp.sqrt(jnp.mean(((x5 - x4) / scale) ** 2))
        accept = err <= 1.0
        factor = jnp.clip(0.9 * (1.0 / jnp.maximum(err, 1e-12)) ** 0.2, 0.2, 5.0)
        h_new = h * factor
        t = jnp.where(accept, t + h, t)
        x = jnp.where(accept, x5, x)
        return (t, x, jnp.maximum(h_new, 1e-8), nfe + 7,
                acc + accept.astype(jnp.int32),
                rej + (1 - accept.astype(jnp.int32)), steps + 1)

    state = (jnp.asarray(t0), x0, jnp.asarray(h0),
             jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32),
             jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32))
    t, x, h, nfe, acc, rej, steps = jax.lax.while_loop(cond, body, state)
    return RK45Result(x1=x, nfe=nfe, accepted=acc, rejected=rej)
