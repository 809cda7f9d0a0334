"""Grouped-query attention with RoPE, masks, and KV-cache decode paths.

Layouts keep KV heads grouped — q is reshaped to (B, L, KV, G, hd) with
G = H / KV — so GQA never materializes repeated K/V (HBM matters: decode is
memory-bound on the cache). Sliding-window decode uses a ring buffer of
``window`` physical slots, which is what makes ``long_500k`` sub-quadratic
for the dense architectures.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from repro.models.layers import apply_rope, dense_init, rms_norm

Array = jax.Array
NEG_INF = -1e30


class KVCache(NamedTuple):
    k: Array       # (B, slots, KV, hd)
    v: Array       # (B, slots, KV, hd)
    index: Array   # int32 tokens already decoded: scalar, or (B,) per-row
    #                (slot serving — each batch row at its own position)

    @property
    def slots(self) -> int:
        return self.k.shape[1]


class PagedKVCache(NamedTuple):
    """vLLM-style paged KV cache: K/V live in a SHARED pool of fixed-size
    pages and each sequence row owns a block-table row mapping its logical
    block index to a physical page id, so resident cache memory per slot is
    the pages the sequence actually uses, not ``max_seq_len`` dense rows.

    Page 0 is RESERVED as the trash page: freed/inactive rows' block-table
    entries point at it, so the write a masked-out row still computes inside
    the one compiled ``step_slots`` program lands in a page nobody attends
    over (the pool has no per-row axis, so it cannot be write-masked the way
    the dense cache's rows are — see ``DecodeEngine._mask_rows``). The page
    allocator (``repro.serving.decode.PageAllocator``) never hands page 0
    out.
    """

    k_pages: Array      # (L, num_pages, KV, page_size, hd) shared pool
    v_pages: Array      # (L, num_pages, KV, page_size, hd)
    block_table: Array  # (B, blocks_per_slot) int32 physical page ids
    index: Array        # (B,) int32 tokens already decoded per row

    @property
    def page_size(self) -> int:
        return self.k_pages.shape[-2]

    @property
    def capacity(self) -> int:
        """Logical positions addressable per row (block-table width x page
        size) — the paged analogue of ``KVCache.slots``."""
        return self.block_table.shape[1] * self.page_size


def init_kv_cache(batch: int, slots: int, n_kv: int, head_dim: int,
                  dtype=jnp.bfloat16) -> KVCache:
    shape = (batch, slots, n_kv, head_dim)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   index=jnp.zeros((), jnp.int32))


def init_attention(key: Array, d_model: int, n_heads: int, n_kv: int,
                   head_dim: int, qk_norm: bool = False) -> dict:
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d_model, n_heads * head_dim),
        "wk": dense_init(ks[1], d_model, n_kv * head_dim),
        "wv": dense_init(ks[2], d_model, n_kv * head_dim),
        "wo": dense_init(ks[3], n_heads * head_dim, d_model),
    }
    if qk_norm:
        p["q_norm"] = jnp.ones((head_dim,), jnp.float32)
        p["k_norm"] = jnp.ones((head_dim,), jnp.float32)
    return p


def _grouped_attend(q: Array, k: Array, v: Array, mask: Optional[Array]) -> Array:
    """q: (B, Lq, KV, G, hd); k, v: (B, Lk, KV, hd); mask: (B?, Lq, Lk) bool."""
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqkgh,bskh->bkgqs", q * scale, k).astype(jnp.float32)
    if mask is not None:
        scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", w, v)
    return out


def _split_heads(x: Array, n: int, hd: int) -> Array:
    return x.reshape(x.shape[:-1] + (n, hd))


def _chunked_attend(q: Array, k: Array, v: Array, positions: Array,
                    causal: bool, window: int, qc: int) -> Array:
    """Blockwise online attention over query chunks (flash-style in XLA):
    bounds score-tensor residency to (B, KV, G, qc, Lk) and never
    materializes the (L, L) mask — per-block masks come from iota compares
    and fuse into the score computation."""
    B, L, KV, G, hd = q.shape
    nq = L // qc
    qb = jnp.moveaxis(q.reshape(B, nq, qc, KV, G, hd), 1, 0)

    def body(_, xs):
        i, qi = xs                                     # qi: (B, qc, KV, G, hd)
        mask = None
        if causal:
            pos_q = jax.lax.dynamic_slice_in_dim(positions, i * qc, qc)
            rel = pos_q[:, None] - positions[None, :]
            mask = rel >= 0
            if window:
                mask = mask & (rel < window)
            mask = jnp.broadcast_to(mask, (B, qc, positions.shape[0]))
        return None, _grouped_attend(qi, k, v, mask)

    _, outs = jax.lax.scan(body, None, (jnp.arange(nq) , qb))
    return jnp.moveaxis(outs, 0, 1).reshape(B, L, KV, G, hd)


def _project(x: Array, w: Array) -> Array:
    """``x @ w`` with its output pinned row-major, so that the dot reads
    ``w`` in the layout it is stored in (see ``attention_forward``)."""
    y = x @ w
    return with_layout_constraint(y, Layout(tuple(range(y.ndim))))


def attention_forward(
    p: dict,
    x: Array,                    # (B, L, d)
    positions: Array,            # (L,) absolute positions
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    rope_theta: float,
    causal: bool = True,
    window: int = 0,
    norm_eps: float = 1e-5,
) -> Array:
    """Full-sequence attention (training / prefill).

    q/k/v are pinned row-major so the RoPE/score layout (B, heads, L, hd)
    relayouts the activations, never the stacked ``wq``/``wk``/``wv``.

    Under an installed sharding context (repro.distributed.context) this
    optionally runs sequence-parallel (query positions sharded on ``model``
    — required when head counts don't divide the tensor axis) and/or
    q-chunked online softmax (long prefill memory).
    """
    from repro.distributed import context

    B, L, _ = x.shape
    G = n_heads // n_kv
    q = _split_heads(_project(x, p["wq"]), n_heads, head_dim)
    k = _split_heads(_project(x, p["wk"]), n_kv, head_dim)
    v = _split_heads(_project(x, p["wv"]), n_kv, head_dim)
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"], norm_eps)
        k = rms_norm(k, p["k_norm"], norm_eps)
    q = apply_rope(q, positions, rope_theta)
    k = apply_rope(k, positions, rope_theta)
    q = q.reshape(B, L, n_kv, G, head_dim)

    seq_par = context.seq_parallel_attn_enabled()
    if seq_par:
        b = context.batch_axis()
        q = context.constrain(q, b, "model", None, None, None)
        k = context.constrain(k, b, None, None, None)
        v = context.constrain(v, b, None, None, None)

    if (context.flash_attention_enabled() and causal and not window
            and L % 256 == 0):
        # Pallas flash attention (no S x S materialization): the compiled
        # kernel on a TPU, the interpreter elsewhere (``kernels.ops``).
        from repro.kernels.flash_attention.ops import attend

        out = attend(q.reshape(B, L, n_heads, head_dim), k, v, causal=True)
        out = out.reshape(B, L, n_kv, G, head_dim)
    elif (qc := context.q_chunk()) and L > qc and L % qc == 0:
        out = _chunked_attend(q, k, v, positions, causal, window, qc)
    else:
        mask = None
        if causal:
            rel = positions[:, None] - positions[None, :]      # (L, L)
            mask = rel >= 0
            if window:
                mask = mask & (rel < window)
            mask = jnp.broadcast_to(mask, (B, L, L))
            if seq_par:
                mask = context.constrain(mask, context.batch_axis(), "model",
                                         None)
        out = _grouped_attend(q, k, v, mask)
    out = out.reshape(B, L, n_heads * head_dim)
    if seq_par:
        # keep query positions sharded through the output projection — the
        # backward of the attention einsums then stays L-sharded (moving the
        # shard to the head dim here made XLA replicate the S x S scores in
        # the gradient computation: §Perf iteration 2).
        out = context.constrain(out, context.batch_axis(), "model", None)
        o = out @ p["wo"]
        return context.constrain(o, context.batch_axis(), None, None)
    return out @ p["wo"]


def decode_attention(
    p: dict,
    x: Array,                    # (B, 1, d) — the new token
    cache: KVCache,
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    rope_theta: float,
    window: int = 0,
    norm_eps: float = 1e-5,
) -> tuple[Array, KVCache]:
    """One-token decode over a KV cache (ring buffer when window > 0).

    ``cache.index`` is either a scalar (the whole batch sits at one decode
    position — the classic engine) or a per-row ``(B,)`` vector (slot
    serving: each batch row is an independent sequence at its own position,
    see ``DecodeEngine.step_slots``). RoPE, the cache write slot, and the
    validity mask are all computed per row, so rows never share position
    state and each row's decode is bit-identical to decoding it alone.
    """
    B, Lq, _ = x.shape
    assert Lq == 1
    G = n_heads // n_kv
    pos = jnp.broadcast_to(cache.index, (B,)).astype(jnp.int32)  # per-row position
    q = _split_heads(x @ p["wq"], n_heads, head_dim)
    k_new = _split_heads(x @ p["wk"], n_kv, head_dim)
    v_new = _split_heads(x @ p["wv"], n_kv, head_dim)
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"], norm_eps)
        k_new = rms_norm(k_new, p["k_norm"], norm_eps)
    posb = pos[:, None]                                         # (B, 1)
    q = apply_rope(q, posb, rope_theta)
    k_new = apply_rope(k_new, posb, rope_theta)

    slot = pos % cache.slots if window else jnp.minimum(pos, cache.slots - 1)
    rows = jnp.arange(B)
    k = cache.k.at[rows, slot].set(k_new[:, 0].astype(cache.k.dtype))
    v = cache.v.at[rows, slot].set(v_new[:, 0].astype(cache.v.dtype))

    # validity of each physical slot, per row
    slot_ids = jnp.arange(cache.slots)
    if window:
        valid = slot_ids[None, :] < jnp.minimum(pos + 1, cache.slots)[:, None]
    else:
        valid = slot_ids[None, :] <= pos[:, None]
    mask = valid[:, None, :]                                    # (B, 1, slots)

    q = q.reshape(B, 1, n_kv, G, head_dim)
    out = _grouped_attend(q, k.astype(q.dtype), v.astype(q.dtype), mask)
    out = out.reshape(B, 1, n_heads * head_dim) @ p["wo"]
    return out, KVCache(k=k, v=v, index=pos + 1)


def decode_attention_paged(
    p: dict,
    x: Array,                    # (B, 1, d) — the new token
    k_pages: Array,              # (num_pages, KV, page_size, hd) one layer
    v_pages: Array,
    block_table: Array,          # (B, nb) int32 page ids
    pos: Array,                  # (B,) int32 decode position per row
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    rope_theta: float,
    norm_eps: float = 1e-5,
    kernel: bool = False,
) -> tuple[Array, Array, Array]:
    """One-token decode over one layer's slice of a paged KV cache.

    The new K/V land at page ``block_table[b, pos // page_size]`` offset
    ``pos % page_size``; attention then runs over the row's own pages only.
    ``kernel=False`` is the dense-gather fallback — it reassembles the
    row-major (B, nb*ps, KV, hd) layout and reuses ``_grouped_attend``, so
    with ``nb * page_size == cache_slots`` its output is BIT-IDENTICAL to
    ``decode_attention`` over the dense cache (same shapes, same ops; masked
    positions are NEG_INF in both paths, so pool garbage never leaks).
    ``kernel=True`` routes through the Pallas paged-attention kernel
    (``kernels.flash_attention.paged_attention``), which DMAs pages via a
    scalar-prefetched block table instead of gathering a dense copy.

    Returns (out, k_pages, v_pages); the caller advances ``index``.
    """
    B, Lq, _ = x.shape
    assert Lq == 1
    G = n_heads // n_kv
    ps = k_pages.shape[2]
    nb = block_table.shape[1]
    q = _split_heads(x @ p["wq"], n_heads, head_dim)
    k_new = _split_heads(x @ p["wk"], n_kv, head_dim)
    v_new = _split_heads(x @ p["wv"], n_kv, head_dim)
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"], norm_eps)
        k_new = rms_norm(k_new, p["k_norm"], norm_eps)
    posb = pos[:, None]                                         # (B, 1)
    q = apply_rope(q, posb, rope_theta)
    k_new = apply_rope(k_new, posb, rope_theta)

    # write the new K/V into each row's own page (clamped like the dense
    # non-windowed path; the gateway rejects over-capacity requests).
    # Inactive rows' block tables point at the reserved trash page 0.
    posw = jnp.minimum(pos, nb * ps - 1)
    rows = jnp.arange(B)
    page = block_table[rows, posw // ps]                        # (B,)
    off = posw % ps
    k_pages = k_pages.at[page, :, off].set(k_new[:, 0].astype(k_pages.dtype))
    v_pages = v_pages.at[page, :, off].set(v_new[:, 0].astype(v_pages.dtype))

    qg = q.reshape(B, n_kv, G, head_dim)
    if kernel:
        from repro.kernels.flash_attention.ops import paged_attend

        out = paged_attend(qg, k_pages, v_pages, block_table, pos + 1)
        out = out.reshape(B, 1, n_heads * head_dim).astype(x.dtype)
    else:
        # dense-gather fallback: row b's logical positions, page-major
        from repro.kernels.flash_attention.ref import gather_pages

        k = gather_pages(k_pages, block_table)
        v = gather_pages(v_pages, block_table)
        valid = jnp.arange(nb * ps)[None, :] <= pos[:, None]
        out = _grouped_attend(qg[:, None], k.astype(q.dtype),
                              v.astype(q.dtype), valid[:, None, :])
        out = out.reshape(B, 1, n_heads * head_dim)
    return out @ p["wo"], k_pages, v_pages


def cross_attention_forward(
    p: dict,
    x: Array,                    # (B, L, d) decoder states
    memory: Array,               # (B, M, d_mem) encoder states (pre-projected keys ok)
    *,
    n_heads: int,
    head_dim: int,
) -> Array:
    """Encoder-decoder cross attention (no mask, no RoPE) — whisper decoder."""
    B, L, _ = x.shape
    q = _split_heads(x @ p["wq"], n_heads, head_dim)
    k = _split_heads(memory @ p["wk"], n_heads, head_dim)
    v = _split_heads(memory @ p["wv"], n_heads, head_dim)
    q = q.reshape(B, L, n_heads, 1, head_dim)
    out = _grouped_attend(q, k, v, None)
    return out.reshape(B, L, n_heads * head_dim) @ p["wo"]
