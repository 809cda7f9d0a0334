"""Pure-jnp oracles for flash attention (GQA, causal) and paged decode
attention (block-table gather)."""
import jax
import jax.numpy as jnp


def attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True) -> jax.Array:
    """q: (B, H, Lq, hd); k, v: (B, KV, Lk, hd)."""
    B, H, Lq, hd = q.shape
    KV, Lk = k.shape[1], k.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, Lq, hd).astype(jnp.float32) * hd**-0.5
    s = jnp.einsum("bkgqh,bksh->bkgqs", qg, k.astype(jnp.float32))
    if causal:
        mask = jnp.arange(Lq)[:, None] >= jnp.arange(Lk)[None, :]
        s = jnp.where(mask, s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bksh->bkgqh", w, v.astype(jnp.float32))
    return o.reshape(B, H, Lq, hd).astype(q.dtype)


def gather_pages(pages: jax.Array, block_table: jax.Array) -> jax.Array:
    """Row-major copy of each row's pages: pool (num_pages, KV, ps, hd) and
    block_table (B, nb) -> (B, nb*ps, KV, hd), row b's logical positions."""
    B, nb = block_table.shape
    _, KV, ps, hd = pages.shape
    rows = pages[block_table].transpose(0, 1, 3, 2, 4)      # (B, nb, ps, KV, hd)
    return rows.reshape(B, nb * ps, KV, hd)


def paged_attention_ref(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                        block_table: jax.Array,
                        lengths: jax.Array) -> jax.Array:
    """Dense-gather oracle for the paged decode kernel.

    q: (B, KV, G, hd); k_pages/v_pages: (num_pages, KV, page_size, hd);
    block_table: (B, nb) int32; lengths: (B,) valid positions per row.
    """
    B, KV, G, hd = q.shape
    ps = k_pages.shape[2]
    nb = block_table.shape[1]
    k = gather_pages(k_pages, block_table).astype(jnp.float32)
    v = gather_pages(v_pages, block_table).astype(jnp.float32)
    qf = q.astype(jnp.float32) * hd ** -0.5
    s = jnp.einsum("bkgh,bskh->bkgs", qf, k)
    valid = jnp.arange(nb * ps)[None, :] < lengths[:, None]
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskh->bkgh", w, v)
    return o.astype(q.dtype)
