"""Unified model API across the architecture pool.

Every family exposes the same four entry points, dispatched on
``cfg.family``:

  init_params(key, cfg)                      -> params pytree
  lm_apply(params, cfg, batch)               -> logits        (train/prefill)
  init_decode_state(cfg, batch, slots, ...)  -> state pytree  (KV cache / RNN state)
  decode_apply(params, cfg, token, state)    -> (logits, state)

plus the paper's substrate:

  velocity(params, cfg, t, x, cond)          -> u_t(x) over latent sequences
  cfm_loss(params, cfg, batch, rng, sched)   -> Conditional Flow Matching loss
                                                (paper eq. 56)

``batch`` is a dict: {"tokens": (B,S) int32} plus "frames" (audio) or
"patches" (vlm) stub-frontend embeddings per the assignment.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.tree_util import Partial

from repro.configs.base import ModelConfig
from repro.core.parametrization import VelocityField
from repro.core.schedulers import Scheduler
from repro.models import mamba2, moe, rwkv6, transformer, vlm, whisper
from repro.models.transformer import latent_targets

Array = jax.Array

FAMILIES = ("dense", "moe", "ssm", "hybrid", "encdec", "vlm")


def init_params(key: Array, cfg: ModelConfig, dtype=None) -> dict:
    return {
        "dense": transformer.init_dense_params,
        "moe": moe.init_moe_params,
        "ssm": rwkv6.init_rwkv_params,
        "hybrid": mamba2.init_hybrid_params,
        "encdec": whisper.init_encdec_params,
        "vlm": vlm.init_vlm_params,
    }[cfg.family](key, cfg, dtype)


def lm_apply(params: dict, cfg: ModelConfig, batch: dict, *,
             window: int = 0, last_only: bool = False) -> Array:
    """Training/prefill logits. ``last_only`` slices the final position
    BEFORE the vocab projection — serving prefill only needs the next-token
    logits, and projecting all 32k positions into a (B, S, V) f32 tensor
    dominates prefill HBM traffic (§Perf iteration)."""
    tokens = batch["tokens"]
    if cfg.family == "dense":
        out = transformer.lm_forward(params, cfg, tokens, window=window,
                                     last_only=last_only)
    elif cfg.family == "moe":
        out, _aux = moe.lm_forward(params, cfg, tokens, window=window,
                                   last_only=last_only)
    elif cfg.family == "ssm":
        out = rwkv6.lm_forward(params, cfg, tokens, last_only=last_only)
    elif cfg.family == "hybrid":
        out = mamba2.lm_forward(params, cfg, tokens, window=window,
                                last_only=last_only)
    elif cfg.family == "encdec":
        out = whisper.lm_forward(params, cfg, tokens, batch["frames"],
                                 last_only=last_only)
    elif cfg.family == "vlm":
        out = vlm.lm_forward(params, cfg, tokens, batch["patches"],
                             window=window, last_only=last_only)
    else:
        raise KeyError(cfg.family)
    return out


def init_decode_state(cfg: ModelConfig, batch: int, slots: int,
                      dtype=jnp.bfloat16, num_frames: int = 1500):
    if cfg.family in ("dense",):
        return transformer.init_caches(cfg, batch, slots, dtype)
    if cfg.family == "moe":
        return transformer.init_caches(cfg, batch, slots, dtype)
    if cfg.family == "ssm":
        return rwkv6.init_state(cfg, batch)
    if cfg.family == "hybrid":
        return mamba2.init_state(cfg, batch, slots, dtype)
    if cfg.family == "encdec":
        return whisper.init_state(cfg, batch, slots, num_frames, dtype)
    if cfg.family == "vlm":
        return vlm.init_state(cfg, batch, slots, dtype)
    raise KeyError(cfg.family)


PAGED_FAMILIES = ("dense", "moe", "vlm")   # KV-cache families that can page


def init_paged_decode_state(cfg: ModelConfig, batch: int, num_pages: int,
                            page_size: int, blocks_per_slot: int,
                            dtype=jnp.bfloat16):
    """Paged decode state (``PagedKVCache``) for the KV-cache families.
    Recurrent/hybrid/encdec state has no pageable KV axis — the SSM family's
    state is already O(1) per slot, and hybrid/encdec are rejected upstream
    (``DecodeEngine``)."""
    if cfg.family not in PAGED_FAMILIES:
        raise TypeError(f"paged KV cache not supported for {cfg.family!r} "
                        f"(pageable families: {PAGED_FAMILIES})")
    return transformer.init_paged_caches(cfg, batch, num_pages, page_size,
                                         blocks_per_slot, dtype)


def decode_state_batch_axes(cfg: ModelConfig, paged: bool = False):
    """Pytree (matching ``init_decode_state``'s structure) of the BATCH axis
    per state leaf — the axis indexed by sequence slot. Slot serving
    (``DecodeEngine.step_slots``) uses this to write-mask, gather, and reset
    individual sequences' state rows without knowing each family's layout.
    ``index`` reads as axis 0 of the per-row ``(B,)`` vector form (scalar
    index states cannot be slot-masked — positions must be per row).

    ``paged=True``: the page POOL leaves have no per-row axis and read as
    ``-1`` — they cannot be row-masked; isolation comes from exclusive
    page ownership plus the reserved trash page (see ``PagedKVCache``), so
    masked steps take the new pool unconditionally and resets leave it
    untouched.
    """
    from repro.models.attention import KVCache, PagedKVCache
    from repro.models.mamba2 import HybridState
    from repro.models.rwkv6 import RWKVState
    from repro.models.whisper import EncDecState

    if paged and cfg.family in PAGED_FAMILIES:
        return PagedKVCache(k_pages=-1, v_pages=-1, block_table=0, index=0)
    if cfg.family in ("dense", "moe", "vlm"):
        return KVCache(k=1, v=1, index=0)
    if cfg.family == "ssm":
        return RWKVState(shift_tm=1, shift_cm=1, wkv=1, index=0)
    if cfg.family == "hybrid":
        return HybridState(conv=1, ssm=1, kv=1, vv=1, index=0)
    if cfg.family == "encdec":
        return EncDecState(k=1, v=1, memory=0, index=0)
    raise KeyError(cfg.family)


def decode_apply(params: dict, cfg: ModelConfig, token: Array, state, *,
                 window: int = 0, paged_kernel: bool = False):
    if cfg.family == "dense":
        return transformer.decode_step(params, cfg, token, state,
                                       window=window,
                                       paged_kernel=paged_kernel)
    if cfg.family == "moe":
        return moe.decode_step(params, cfg, token, state, window=window,
                               paged_kernel=paged_kernel)
    if cfg.family == "ssm":
        return rwkv6.decode_step(params, cfg, token, state)
    if cfg.family == "hybrid":
        return mamba2.decode_step(params, cfg, token, state, window=window)
    if cfg.family == "encdec":
        return whisper.decode_step(params, cfg, token, state)
    if cfg.family == "vlm":
        return vlm.decode_step(params, cfg, token, state, window=window,
                               paged_kernel=paged_kernel)
    raise KeyError(cfg.family)


# ---------------------------------------------------------------------------
# Flow mode: the backbone as velocity field u_t(x) — the paper's substrate
# ---------------------------------------------------------------------------


def _hidden_fn(cfg: ModelConfig, batch: Optional[dict], remat: bool = False):
    """Family-specific hidden-state function for the flow head."""
    if cfg.family == "dense":
        return lambda p, c, h, pos: transformer.dense_hidden(p, c, h, pos,
                                                             remat=remat)
    if cfg.family == "vlm":
        def fn(p, c, h, pos):
            # condition on the (stub) vision patches as a sequence prefix
            if batch is not None and "patches" in batch:
                pre = vlm.project_patches(p, batch["patches"]).astype(h.dtype)
                m = pre.shape[1]
                h = jnp.concatenate([pre, h], axis=1)
                out = transformer.dense_hidden(
                    p, c, h, jnp.arange(h.shape[1]), remat=remat)
                return out[:, m:]
            return transformer.dense_hidden(p, c, h, pos, remat=remat)
        return fn
    if cfg.family == "moe":
        return lambda p, c, h, pos: moe.moe_hidden(p, c, h, pos, remat=remat)[0]
    if cfg.family == "ssm":
        return lambda p, c, h, pos: rwkv6.rwkv_hidden(p, c, h, remat=remat)
    if cfg.family == "hybrid":
        return lambda p, c, h, pos: mamba2.hybrid_hidden(p, c, h, pos,
                                                         remat=remat)
    if cfg.family == "encdec":
        def fn(p, c, h, pos):
            memory = whisper.encode(p, c, batch["frames"], remat=remat)
            return whisper.decoder_hidden(p, c, h, memory, pos, remat=remat)
        return fn
    raise KeyError(cfg.family)


def velocity(params: dict, cfg: ModelConfig, t: Array, x: Array,
             batch: Optional[dict] = None, *, remat: bool = False) -> Array:
    """u_t(x): x (B, S, latent_dim) -> velocity. ``batch`` provides the
    conditioning (tokens / frames / patches); None = unconditional (CFG)."""
    cond = batch.get("tokens") if batch else None
    return transformer.flow_velocity(params, cfg, t, x, cond,
                                     hidden_fn=_hidden_fn(cfg, batch, remat))


def velocity_field(params: dict, cfg: ModelConfig, sched: Scheduler,
                   batch: Optional[dict] = None, *, cfg_scale: float = 0.0
                   ) -> VelocityField:
    """Wrap the model for the BNS sampler, with classifier-free guidance.

    ``fn`` is a ``jax.tree_util.Partial`` whose leaves are ``params`` and
    ``batch``: a jit that takes the field's ``fn`` as an argument receives
    the backbone weights as inputs instead of baking them into the program
    as constants (gigabytes of HLO literals at published widths)."""
    return VelocityField(
        fn=Partial(functools.partial(_guided_velocity, cfg=cfg,
                                     cfg_scale=cfg_scale), params, batch),
        scheduler=sched)


def _guided_velocity(params: dict, batch: Optional[dict], t: Array, x: Array,
                     *, cfg: ModelConfig, cfg_scale: float) -> Array:
    uc = velocity(params, cfg, t, x, batch)
    if cfg_scale == 0.0:
        return uc
    uu = velocity(params, cfg, t, x, None)
    return (1.0 + cfg_scale) * uc - cfg_scale * uu


def cfm_loss(params: dict, cfg: ModelConfig, batch: dict, rng: Array,
             sched: Scheduler, *, p_uncond: float = 0.1,
             remat: bool = False) -> Array:
    """Conditional Flow Matching loss (paper eq. 56) over latent sequences.

    x1 = latent embedding of the data tokens; x_t = sigma_t x0 + alpha_t x1;
    target velocity = sigma'_t x0 + alpha'_t x1.
    """
    from repro.distributed import context

    tokens = batch["tokens"]
    B, S = tokens.shape
    k_t, k_x0, k_drop = jax.random.split(rng, 3)
    x1 = latent_targets(params, tokens).astype(jnp.float32)
    # RNG-generated tensors default to replicated under GSPMD — pin the batch
    # sharding here or it poisons every downstream activation (§Perf iter 3).
    b = context.batch_axis()
    x0 = jax.random.normal(k_x0, x1.shape, jnp.float32)
    x0 = context.constrain(x0, b, None, None)
    t = jax.random.uniform(k_t, (B,))
    t = context.constrain(t, b)
    tb = t[:, None, None]
    a, s = sched.alpha(tb), sched.sigma(tb)
    da, ds = sched.dalpha(tb), sched.dsigma(tb)
    x_t = s * x0 + a * x1
    target = ds * x0 + da * x1
    # CFG training: drop conditioning with prob p_uncond (paper's P-Uncond)
    drop = jax.random.bernoulli(k_drop, p_uncond, (B,))
    cond_tokens = jnp.where(drop[:, None], jnp.zeros_like(tokens), tokens)
    v = velocity(params, cfg, t, x_t.astype(jnp.float32),
                 {**batch, "tokens": cond_tokens}, remat=remat)
    return jnp.mean((v.astype(jnp.float32) - target) ** 2)
