"""Pallas TPU kernel for the NS solver update rule (paper eq. 11):

    x_{i+1} = a * x0 + sum_{j<=i} w_j U_j

This is the paper's own compute primitive — a memory-bound weighted reduction
over the stored velocity buffer U (n, B, D). Unfused, XLA materializes the
masked-weight broadcast and reads U once per add; the kernel streams each
(block_b, block_d) tile of all n velocity rows through VMEM once and writes
one output tile.

VMEM budget per grid step: (n+1) * block_b * block_d * 4B
(n<=20, 8x512 tiles -> ~344 KiB, well under the ~16 MiB/core budget), with
block_d a multiple of 128 for lane alignment.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _kernel(coeff_ref, x0_ref, u_ref, o_ref, *, n: int):
    # coeff_ref: (n+1,) in SMEM — [a, w_0..w_{n-1}]
    acc = coeff_ref[0] * x0_ref[...].astype(jnp.float32)
    for j in range(n):
        acc += coeff_ref[j + 1] * u_ref[j].astype(jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "block_d", "interpret"))
def ns_update(x0: Array, u: Array, a: Array, w: Array, *,
              block_b: int = 8, block_d: int = 512,
              interpret: bool) -> Array:
    """x0: (B, D); u: (n, B, D); a: scalar; w: (n,). Returns (B, D).

    Rows of ``w`` beyond the current step must already be zero (the caller
    masks), so the kernel is oblivious to the step index.
    """
    n, B, D = u.shape
    block_b = min(block_b, B)
    block_d = min(block_d, D)
    assert B % block_b == 0 and D % block_d == 0, (B, D, block_b, block_d)
    coeff = jnp.concatenate([a.reshape(1), w]).astype(jnp.float32)
    grid = (B // block_b, D // block_d)
    return pl.pallas_call(
        functools.partial(_kernel, n=n),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block_b, block_d), lambda i, j: (i, j)),
            pl.BlockSpec((n, block_b, block_d), lambda i, j: (0, i, j)),
        ],
        out_specs=pl.BlockSpec((block_b, block_d), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, D), x0.dtype),
        interpret=interpret,
    )(coeff, x0, u)


def ns_update_nd(x0: Array, u: Array, a: Array, w: Array, *,
                 interpret: bool) -> Array:
    """Arbitrary trailing dims: x0 (B, ...), u (n, B, ...)."""
    shape = x0.shape
    x2 = x0.reshape(shape[0], -1)
    u2 = u.reshape(u.shape[0], shape[0], -1)
    # pad feature dim to a 128 multiple for lane alignment
    D = x2.shape[1]
    pad = (-D) % 128
    if pad:
        x2 = jnp.pad(x2, ((0, 0), (0, pad)))
        u2 = jnp.pad(u2, ((0, 0), (0, 0), (0, pad)))
    bd = 512 if (D + pad) % 512 == 0 else 128
    # a batch block must tile the TPU's sublanes: a multiple of 8 rows, or
    # the whole batch (any size is legal when the block spans the dim)
    bb = 8 if shape[0] % 8 == 0 else shape[0]
    out = ns_update(x2, u2, a, w, block_b=bb, block_d=bd, interpret=interpret)
    return out[:, :D].reshape(shape)
