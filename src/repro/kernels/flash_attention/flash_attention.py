"""Pallas TPU flash attention (causal, GQA) — the one-NFE hot spot.

Online-softmax blocked attention: grid (B, H, Lq/bq, Lk/bk) with the KV-block
axis innermost (sequential on TPU), carrying running max / normalizer /
accumulator in VMEM scratch. Fully-masked causal blocks are predicated out
with ``pl.when`` (upper-triangular block skips — ~2x on long prefill).

GQA is handled in the index map: KV head = q_head // group, so K/V tiles are
never physically repeated. Block shapes default to (128, head_dim) — MXU
aligned (head_dim is 64/80/128 across the pool; 128-multiple lanes come from
bk; for hd=80 archs the MXU pads, noted in DESIGN.md).

VMEM per step: q,k,v tiles + acc ~ (3*bk + 2*bq) * hd * 4B  (~0.5 MiB at
128/128/128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            bq: int, bk: int, scale: float, n_k: int, causal: bool):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal block skip: block fully in the future
    run = (not causal) or (ik * bk <= iq * bq + bq - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)                  # (bk, hd)
        s = q @ k.T                                          # (bq, bk)
        if causal:
            qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_scr[...]                                  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, -1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + p @ v
        m_scr[...] = m_new

    @pl.when(ik == n_k - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                    bq: int = 128, bk: int = 128,
                    interpret: bool) -> Array:
    """q: (B, H, Lq, hd); k, v: (B, KV, Lk, hd); H % KV == 0. Returns q-shaped."""
    B, H, Lq, hd = q.shape
    KV, Lk = k.shape[1], k.shape[2]
    G = H // KV
    bq = min(bq, Lq)
    bk = min(bk, Lk)
    assert Lq % bq == 0 and Lk % bk == 0
    n_k = Lk // bk
    grid = (B, H, Lq // bq, n_k)
    scale = hd ** -0.5
    kernel = functools.partial(_kernel, bq=bq, bk=bk, scale=scale, n_k=n_k,
                               causal=causal)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
