"""Pallas TPU kernel for the chunked gated linear recurrence (RWKV6 / Mamba2).

    S_t = diag(exp(ld_t)) S_{t-1} + k_t v_t^T ;  o_t = q_t^T S_{t or t-1}

Grid: (B*H, L/chunk) with the chunk axis innermost (sequential) — the (dk,dv)
state lives in VMEM scratch across chunk steps, so the recurrence makes ONE
pass over HBM (the pure-jnp chunked form re-materializes the (c, c, dk) decay
tensor in HBM per chunk; here it stays in VMEM).

All decay exponents are differences of within-chunk cumulative log-decays,
non-positive under the causal mask — numerically bounded for arbitrarily
strong decay (same scheme as the jnp reference).

VMEM per step: chunk*(2 dk + dv) tiles + (c, c, dk) decay cube + (dk, dv)
state: 64*64*64*4B = 1 MiB cube at the default chunk=64, dk=64.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _kernel(q_ref, k_ref, v_ref, ld_ref, o_ref, s_out_ref, s_scr, *,
            chunk: int, inclusive: bool, n_chunks: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    q = q_ref[0].astype(jnp.float32)            # (c, dk)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)            # (c, dv)
    ld = ld_ref[0].astype(jnp.float32)          # (c, dk)

    cum = jnp.cumsum(ld, axis=0)                # (c, dk)
    cum_q = cum if inclusive else cum - ld
    S = s_scr[...]                              # (dk, dv)

    o_cross = (q * jnp.exp(cum_q)) @ S          # (c, dv)

    dd = cum_q[:, None, :] - cum[None, :, :]    # (c, c, dk)
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = (t_idx >= s_idx) if inclusive else (t_idx > s_idx)
    scores = jnp.einsum("td,sd,tsd->ts", q, k, jnp.exp(jnp.minimum(dd, 0.0)))
    scores = jnp.where(tri, scores, 0.0)
    o_ref[0] = (o_cross + scores @ v).astype(o_ref.dtype)

    cum_end = cum[-1:, :]                       # (1, dk)
    k_scaled = k * jnp.exp(cum_end - cum)       # (c, dk)
    s_scr[...] = jnp.exp(cum_end[0])[:, None] * S + k_scaled.T @ v

    @pl.when(ic == n_chunks - 1)
    def _final():
        s_out_ref[0] = s_scr[...]


@functools.partial(jax.jit,
                   static_argnames=("inclusive", "chunk", "interpret"))
def gla_scan(q: Array, k: Array, v: Array, ld: Array, *,
             inclusive: bool = True, chunk: int = 64,
             interpret: bool) -> tuple[Array, Array]:
    """q, k, ld: (B, L, H, dk); v: (B, L, H, dv); L % chunk == 0.

    Returns (o: (B, L, H, dv), final state: (B, H, dk, dv))."""
    B, L, H, dk = q.shape
    dv = v.shape[-1]
    chunk = min(chunk, L)
    assert L % chunk == 0, (L, chunk)
    n_chunks = L // chunk

    def flat(a):
        return a.transpose(0, 2, 1, 3).reshape(B * H, L, a.shape[-1])

    qf, kf, vf, ldf = map(flat, (q, k, v, ld))
    grid = (B * H, n_chunks)
    kernel = functools.partial(_kernel, chunk=chunk, inclusive=inclusive,
                               n_chunks=n_chunks)
    o, s_fin = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, chunk, d), lambda bh, ic: (bh, ic, 0))
                  for d in (dk, dk, dv, dk)],
        out_specs=[
            pl.BlockSpec((1, chunk, dv), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, dk, dv), lambda bh, ic: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, L, dv), v.dtype),
            jax.ShapeDtypeStruct((B * H, dk, dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf, ldf)
    o = o.reshape(B, H, L, dv).transpose(0, 2, 1, 3)
    return o, s_fin.reshape(B, H, dk, dv)
