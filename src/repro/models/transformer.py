"""Dense decoder-only transformer family (llama-style GQA).

Covers yi-6b / yi-34b [arXiv:2403.04652], phi3-medium-14b [arXiv:2404.14219],
command-r-35b (parallel attn+FFN block, no biases)
[hf:CohereForAI/c4ai-command-r-v01], and the InternLM2-style LM of
internvl2-26b [arXiv:2404.16821].

Layer params are stacked on a leading axis and the forward pass is a
``lax.scan`` over layers — one compiled block body regardless of depth, which
keeps dry-run HLO size flat across the 32-94 layer pool.

Two heads:
  * LM head      — ``lm_forward`` / ``decode_step`` (serving substrate);
  * velocity head — ``flow_velocity`` (the paper's flow-matching substrate).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.attention import (
    KVCache,
    PagedKVCache,
    attention_forward,
    decode_attention,
    decode_attention_paged,
    init_attention,
    init_kv_cache,
)
from repro.models.layers import (
    dense_init,
    rms_norm,
    swiglu,
    timestep_embedding,
)

Array = jax.Array


def _layer_init(key: Array, cfg: ModelConfig) -> dict:
    hd = cfg.resolved_head_dim
    k_attn, k1, k2, k3 = jax.random.split(key, 4)
    p = {
        "attn": init_attention(k_attn, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                               hd, cfg.qk_norm),
        "mlp": {
            "w_gate": dense_init(k1, cfg.d_model, cfg.d_ff),
            "w_up": dense_init(k2, cfg.d_model, cfg.d_ff),
            "w_down": dense_init(k3, cfg.d_ff, cfg.d_model),
        },
        "norm1": jnp.ones((cfg.d_model,), jnp.float32),
    }
    if not cfg.parallel_block:
        p["norm2"] = jnp.ones((cfg.d_model,), jnp.float32)
    return p


def init_flow_head(key: Array, cfg: ModelConfig) -> dict:
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    return {
        "latent_embed": dense_init(k1, cfg.vocab, cfg.latent_dim, scale=1.0),
        "proj_in": dense_init(k2, cfg.latent_dim, cfg.d_model),
        "proj_out": dense_init(k3, cfg.d_model, cfg.latent_dim),
        "time_w1": dense_init(k4, cfg.d_model, cfg.d_model),
        "time_w2": dense_init(k5, cfg.d_model, cfg.d_model),
    }


def init_dense_params(key: Array, cfg: ModelConfig, dtype=None) -> dict:
    dtype = dtype or jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, cfg.n_layers + 3)
    # vmapped over the layer keys: the same values as stacking per-layer
    # inits, in one program body instead of n_layers unrolled copies
    layers = jax.vmap(lambda k: _layer_init(k, cfg))(keys[:cfg.n_layers])
    params = {
        "embed": dense_init(keys[-3], cfg.vocab, cfg.d_model, scale=1.0),
        "layers": layers,
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
        "flow": init_flow_head(keys[-1], cfg),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[-2], cfg.d_model, cfg.vocab)
    return cast_params(params, dtype)


def cast_params(params, dtype):
    """Cast matmul weights; keep norm scales (1-D) in fp32."""
    return jax.tree.map(
        lambda x: x if x.ndim == 1 else x.astype(dtype), params)


def _block(p: dict, cfg: ModelConfig, h: Array, positions: Array,
           causal: bool, window: int) -> Array:
    hd = cfg.resolved_head_dim
    attn_kw = dict(n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=hd,
                   rope_theta=cfg.rope_theta, causal=causal, window=window,
                   norm_eps=cfg.norm_eps)
    if cfg.parallel_block:
        hn = rms_norm(h, p["norm1"], cfg.norm_eps)
        return h + attention_forward(p["attn"], hn, positions, **attn_kw) \
                 + swiglu(hn, **p["mlp"])
    h = h + attention_forward(p["attn"], rms_norm(h, p["norm1"], cfg.norm_eps),
                              positions, **attn_kw)
    h = h + swiglu(rms_norm(h, p["norm2"], cfg.norm_eps), **p["mlp"])
    return h


def dense_hidden(params: dict, cfg: ModelConfig, h: Array, positions: Array,
                 *, causal: bool = True, window: int = 0,
                 remat: bool = False) -> Array:
    def body(h, layer_p):
        return _block(layer_p, cfg, h, positions, causal, window), None

    if remat:
        body = jax.checkpoint(body)
    h, _ = jax.lax.scan(body, h, params["layers"])
    return rms_norm(h, params["final_norm"], cfg.norm_eps)


def lm_forward(params: dict, cfg: ModelConfig, tokens: Array,
               positions: Optional[Array] = None, *, window: int = 0,
               extra_embeds: Optional[Array] = None,
               last_only: bool = False) -> Array:
    """Training / prefill: logits for every position. ``extra_embeds`` is the
    VLM/audio path: stub embeddings prepended to the token embeddings."""
    h = params["embed"][tokens]
    if extra_embeds is not None:
        h = jnp.concatenate([extra_embeds.astype(h.dtype), h], axis=1)
    L = h.shape[1]
    if positions is None:
        positions = jnp.arange(L)
    h = dense_hidden(params, cfg, h, positions, causal=True, window=window)
    if last_only:
        h = h[:, -1:, :]
    if cfg.tie_embeddings:
        return h @ params["embed"].T
    return h @ params["lm_head"]


def init_caches(cfg: ModelConfig, batch: int, slots: int, dtype=jnp.bfloat16) -> KVCache:
    hd = cfg.resolved_head_dim
    one = init_kv_cache(batch, slots, cfg.n_kv_heads, hd, dtype)
    return KVCache(
        k=jnp.zeros((cfg.n_layers,) + one.k.shape, dtype),
        v=jnp.zeros((cfg.n_layers,) + one.v.shape, dtype),
        index=jnp.zeros((), jnp.int32),
    )


def init_paged_caches(cfg: ModelConfig, batch: int, num_pages: int,
                      page_size: int, blocks_per_slot: int,
                      dtype=jnp.bfloat16) -> PagedKVCache:
    """Paged decode state: a shared (L, num_pages, KV, page_size, hd) pool
    plus a zeroed per-row block table — all rows start on the reserved
    trash page 0 (see ``PagedKVCache``) until the gateway's page allocator
    assigns them real pages at admission. Each page of one KV head is a
    contiguous (page_size, hd) tile, the paged-attention kernel's block."""
    hd = cfg.resolved_head_dim
    shape = (cfg.n_layers, num_pages, cfg.n_kv_heads, page_size, hd)
    return PagedKVCache(
        k_pages=jnp.zeros(shape, dtype),
        v_pages=jnp.zeros(shape, dtype),
        block_table=jnp.zeros((batch, blocks_per_slot), jnp.int32),
        index=jnp.zeros((batch,), jnp.int32),
    )


def decode_step(params: dict, cfg: ModelConfig, token: Array, caches,
                *, window: int = 0,
                paged_kernel: bool = False):
    """One-token decode: token (B,) int32 -> (logits (B, V), new caches).

    ``caches`` is a dense ``KVCache`` or a ``PagedKVCache``; the layer scan
    carries each layer's cache slice either way (dense rows vs page-pool
    slices + the shared block table)."""
    h = params["embed"][token][:, None, :]                     # (B, 1, d)
    hd = cfg.resolved_head_dim
    paged = isinstance(caches, PagedKVCache)
    if paged:
        pos = jnp.broadcast_to(caches.index, (h.shape[0],)).astype(jnp.int32)
        attn_kw = dict(n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=hd,
                       rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps,
                       kernel=paged_kernel)
    else:
        attn_kw = dict(n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=hd,
                       rope_theta=cfg.rope_theta, window=window,
                       norm_eps=cfg.norm_eps)

    def body(carry, xs):
        h = carry
        layer_p, k_c, v_c = xs
        hn = rms_norm(h, layer_p["norm1"], cfg.norm_eps)
        if paged:
            attn_out, k_c, v_c = decode_attention_paged(
                layer_p["attn"], hn, k_c, v_c, caches.block_table, pos,
                **attn_kw)
        else:
            cache = KVCache(k=k_c, v=v_c, index=caches.index)
            attn_out, cache = decode_attention(layer_p["attn"], hn, cache,
                                               **attn_kw)
            k_c, v_c = cache.k, cache.v
        if cfg.parallel_block:
            h = h + attn_out + swiglu(hn, **layer_p["mlp"])
        else:
            h = h + attn_out
            h = h + swiglu(rms_norm(h, layer_p["norm2"], cfg.norm_eps),
                           **layer_p["mlp"])
        return h, (k_c, v_c)

    kv_in = (caches.k_pages, caches.v_pages) if paged else (caches.k, caches.v)
    h, (ks, vs) = jax.lax.scan(body, h, (params["layers"],) + kv_in)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)[:, 0, :]
    logits = h @ (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    if paged:
        return logits, PagedKVCache(k_pages=ks, v_pages=vs,
                                    block_table=caches.block_table,
                                    index=pos + 1)
    return logits, KVCache(k=ks, v=vs, index=caches.index + 1)


# ---------------------------------------------------------------------------
# Flow mode — the backbone as a velocity field u_t(x) (paper substrate)
# ---------------------------------------------------------------------------


def flow_velocity(params: dict, cfg: ModelConfig, t: Array, x: Array,
                  cond_tokens: Optional[Array], *,
                  hidden_fn=None, remat: bool = False) -> Array:
    """u_t(x): x (B, S, latent_dim) noisy latents -> velocity, same shape.

    Conditioning: token embeddings added to the input projection (class/text
    conditioning analog); ``cond_tokens=None`` is the unconditional branch
    (CFG). ``hidden_fn`` lets non-dense families reuse this head."""
    f = params["flow"]
    h = x.astype(f["proj_in"].dtype) @ f["proj_in"]
    if cond_tokens is not None:
        h = h + params["embed"][cond_tokens]
    temb = timestep_embedding(t, cfg.d_model).astype(h.dtype)
    temb = jax.nn.silu(temb @ f["time_w1"]) @ f["time_w2"]
    h = h + temb[:, None, :] if temb.ndim == 2 else h + temb[None, None, :]
    positions = jnp.arange(x.shape[1])
    if hidden_fn is None:
        h = dense_hidden(params, cfg, h, positions, causal=True, remat=remat)
    else:
        h = hidden_fn(params, cfg, h, positions)
    return (h @ f["proj_out"]).astype(x.dtype)


def latent_targets(params: dict, tokens: Array) -> Array:
    """x1 = latent embedding of the data tokens (flow-matching target)."""
    return params["flow"]["latent_embed"][tokens]
