"""Pallas TPU paged attention — decode-step attention over a paged KV cache.

vLLM-style PagedAttention: K/V live in a shared pool of fixed-size pages
(``k_pages``/``v_pages``: (num_pages, KV, page_size, hd)) and each sequence
owns a per-slot row of a BLOCK TABLE mapping its logical block index to a
physical page id. One decode step attends each query row over its own pages
only, so per-slot cache memory is the pages the sequence actually uses, not
``max_seq_len`` dense rows.

The block table and per-row lengths ride ``pltpu.PrefetchScalarGridSpec``
scalar prefetch: they are available BEFORE the kernel body, so the K/V
BlockSpec index maps resolve ``block_table[b, i]`` to the physical page to
DMA — the gather never materializes a dense per-row cache. Grid is
(B, KV_heads, num_blocks) with the block axis innermost (sequential on TPU),
carrying the online-softmax running max / normalizer / accumulator for the
G = H/KV grouped query heads in VMEM scratch, exactly like the prefill
flash-attention kernel one file over. Blocks fully past a row's length are
predicated out with ``pl.when`` (the decode twin of the causal block skip).

Rows that are shorter than the pool's widest resident sequence pay only
their own pages: the skip guard reads ``lengths[b]`` from the prefetched
scalars. ``interpret=True`` runs the same kernel off-TPU (CI).

Each K/V block is one page of one KV head: its last two dims,
``(page_size, hd)``, span the pool's last two dims, which the TPU lowering
accepts for every page size and dtype (a block that is not a multiple of
the (8, 128) tile must equal the array's dims). Pages that are a multiple
of the dtype's sublane tile (8 rows of 32-bit, 16 of 16-bit) waste no
padding in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array
NEG_INF = -1e30


def _kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, ps: int, nb: int, scale: float):
    b = pl.program_id(0)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]

    # page skip: this row's sequence ends before this block
    @pl.when(i * ps < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)                  # (ps, hd)
        v = v_ref[0, 0].astype(jnp.float32)                  # (ps, hd)
        s = q @ k.T                                          # (G, ps)
        kpos = i * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < length, s, NEG_INF)
        m_prev = m_scr[...]                                  # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, -1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + p @ v
        m_scr[...] = m_new

    @pl.when(i == nb - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q: Array, k_pages: Array, v_pages: Array,
                    block_table: Array, lengths: Array, *,
                    interpret: bool) -> Array:
    """One-token paged decode attention.

    q: (B, KV, G, hd) grouped query heads; k_pages/v_pages:
    (num_pages, KV, page_size, hd) shared page pool; block_table: (B, nb)
    int32 physical page ids per logical block; lengths: (B,) int32 valid
    positions per row (the current token already written). Returns
    (B, KV, G, hd).
    """
    B, KV, G, hd = q.shape
    ps = k_pages.shape[2]
    nb = block_table.shape[1]
    scale = hd ** -0.5
    kernel = functools.partial(_kernel, ps=ps, nb=nb, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,           # block_table, lengths
        grid=(B, KV, nb),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd),
                         lambda b, h, i, bt, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, ps, hd),
                         lambda b, h, i, bt, ln: (bt[b, i], h, 0, 0)),
            pl.BlockSpec((1, 1, ps, hd),
                         lambda b, h, i, bt, ln: (bt[b, i], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd),
                               lambda b, h, i, bt, ln: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(block_table.astype(jnp.int32), lengths.astype(jnp.int32),
      q, k_pages, v_pages)
