"""Sharding rules: parameter and activation PartitionSpecs per architecture.

Strategy (Megatron + FSDP, expert-parallel for MoE):
  * ``model`` axis — tensor parallelism: attention head dims and FFN hidden
    dims column/row sharded; MoE experts sharded (expert parallelism);
    vocab sharded when divisible.
  * batch axes (``data``, composed with ``pod`` on multi-pod meshes) — batch
    sharding for activations and FSDP sharding for weights/optimizer state
    (XLA inserts the per-layer all-gathers inside the layer scan).

Rules are path-pattern based so every family in the zoo is covered by one
table; anything unmatched is replicated (norm scales, biases, small heads).
"""
from __future__ import annotations

import re
from typing import Sequence

import jax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
    return "/".join(parts)


# (regex, spec builder). ``b`` = composed batch/FSDP axes tuple (or None on
# 1-axis meshes), "model" literal for the tensor axis. Specs are written for
# STACKED layer params (leading L axis); the leading None also matches
# unstacked 2-D tensors because GSPMD right-aligns...  we instead generate
# specs of exactly the right rank in ``spec_for``.
_COL = "col"      # (..., d_in, d_out_sharded)   -> P(*, b, model)
_ROW = "row"      # (..., d_in_sharded, d_out)   -> P(*, model, b)
_EXPERT_COL = "expert_col"   # (L, E, d, d_e) -> P(None, model, b, None)
_EXPERT_ROW = "expert_row"   # (L, E, d_e, d) -> P(None, model, None, b)
_VOCAB_IN = "vocab_in"       # (V, d) embeddings
_VOCAB_OUT = "vocab_out"     # (d, V) lm head
_HEADS = "heads"             # (L, nheads) per-head scalars
_DINNER = "dinner"           # (L, d_inner) vectors sharded on model
_REPL = "repl"

_RULES: Sequence[tuple[str, str]] = (
    (r".*attn/wq$", _COL),
    (r".*attn/wk$", _COL),
    (r".*attn/wv$", _COL),
    (r".*attn/wo$", _ROW),
    (r".*mlp/w_gate$", _COL),
    (r".*mlp/w_up$", _COL),
    (r".*mlp/w_down$", _ROW),
    (r".*mlp/w1$", _COL),
    (r".*mlp/w2$", _ROW),
    (r".*moe/router$", "router"),
    (r".*moe/w_gate$", _EXPERT_COL),
    (r".*moe/w_up$", _EXPERT_COL),
    (r".*moe/w_down$", _EXPERT_ROW),
    (r".*tm/w[rkvg]$", _COL),
    (r".*tm/wo$", _ROW),
    (r".*cm/wk$", _COL),
    (r".*cm/wv$", _ROW),
    (r".*cm/wr$", _COL),
    (r".*in_proj$", _COL),
    (r".*out_proj$", _ROW),
    (r".*conv_w$", "conv"),
    (r".*(A_log|dt_bias|/D)$", _HEADS),
    (r".*gate_norm$", _DINNER),
    (r".*projector/w[12]$", _COL),
    (r"^embed$", _VOCAB_IN),
    (r".*latent_embed$", _VOCAB_IN),
    (r"^lm_head$", _VOCAB_OUT),
    (r".*proj_in$", _COL),
    (r".*proj_out$", _ROW),
    (r".*time_w1$", _COL),
    (r".*time_w2$", _ROW),
)


def param_specs(params_shape, cfg: ModelConfig, mesh) -> object:
    """Pytree of PartitionSpec matching ``params_shape`` (shapes or arrays)."""
    from repro.launch.mesh import batch_axes

    b = batch_axes(mesh)
    b = b if len(b) > 1 else (b[0] if b else None)
    model_parts = mesh.shape["model"]

    def spec_for(path, leaf) -> P:
        name = _path_str(path)
        ndim = len(leaf.shape)
        kind = _REPL
        for pat, k in _RULES:
            if re.match(pat, name):
                kind = k
                break
        if kind == _REPL or ndim <= 1:
            return P()
        if kind == _COL:
            # (..., d_in, d_out): FSDP on d_in, tensor on d_out — if divisible
            din, dout = leaf.shape[-2], leaf.shape[-1]
            fsdp = b if _div(din, mesh, b) else None
            tp = "model" if dout % model_parts == 0 else None
            return P(*(None,) * (ndim - 2), fsdp, tp)
        if kind == _ROW:
            din, dout = leaf.shape[-2], leaf.shape[-1]
            tp = "model" if din % model_parts == 0 else None
            fsdp = b if _div(dout, mesh, b) else None
            return P(*(None,) * (ndim - 2), tp, fsdp)
        if kind == _EXPERT_COL:
            return P(None, "model", b, None)
        if kind == _EXPERT_ROW:
            return P(None, "model", None, b)
        if kind == "router":
            return P(*(None,) * (ndim - 2), b, None)
        if kind == "conv":           # (L, k, conv_dim)
            return P(*(None,) * (ndim - 1), "model")
        if kind == _HEADS:           # (L, n_heads)
            nh = leaf.shape[-1]
            return P(*(None,) * (ndim - 1),
                     "model" if nh % model_parts == 0 else None)
        if kind == _DINNER:
            return P(*(None,) * (ndim - 1), "model")
        if kind == _VOCAB_IN:        # (V, d)
            v = leaf.shape[0]
            return P("model" if v % model_parts == 0 else None, b)
        if kind == _VOCAB_OUT:       # (d, V)
            v = leaf.shape[-1]
            return P(b, "model" if v % model_parts == 0 else None)
        return P()

    return jax.tree_util.tree_map_with_path(spec_for, params_shape)


def _div(dim: int, mesh, b) -> object:
    if b is None:
        return False
    axes = (b,) if isinstance(b, str) else b
    total = 1
    for a in axes:
        total *= mesh.shape[a]
    return dim % total == 0


def state_specs(state_shape, cfg: ModelConfig, mesh, batch: int):
    """PartitionSpecs for decode state pytrees (KV caches / recurrent states).

    Matches on leaf rank/shape within the known state NamedTuples:
      KVCache.k/v           (L, B, slots, KV, hd)
      PagedKVCache.k_pages/v_pages  (L, num_pages, KV, page_size, hd)
        — no batch axis (the pool is shared by every row); shard the KV
        heads on ``model`` when divisible, like the dense cache. The block
        table / per-row index stay replicated: every shard needs the full
        routing to gather its head-shard of any page.
      RWKVState.shift_*     (L, B, d)        wkv (L, B, H, dk, dv)
      HybridState.conv      (L, B, k, conv)  ssm (L, B, nh, ds, hd)
      EncDecState.memory    (B, M, d)
    """
    from repro.launch.mesh import batch_axes

    b = batch_axes(mesh)
    b = b if len(b) > 1 else b[0]
    bt = 1
    for a in (b if isinstance(b, tuple) else (b,)):
        bt *= mesh.shape[a]
    batch_s = b if (batch % bt == 0 and batch >= bt) else None
    mp = mesh.shape["model"]

    def spec_for(path, leaf) -> P:
        name = _path_str(path)
        nd = len(leaf.shape)
        if nd == 0:
            return P()                                     # index scalar
        if name in ("k_pages", "v_pages"):     # (L, pages, KV, page_size, hd)
            kv = leaf.shape[2]
            return P(None, None, "model" if kv % mp == 0 else None,
                     None, None)
        if name == "block_table":                          # (B, blocks) int32
            return P()
        if name in ("k", "v", "kv", "vv"):                 # (L/sites,B,slots,KV,hd)
            kv = leaf.shape[3]
            if kv % mp == 0:
                return P(None, batch_s, None, "model", None)
            if batch_s is None:
                return P(None, None, b, None, None)        # seq-sharded decode
            return P(None, batch_s, "model", None, None)
        if name == "memory":                               # (B, M, d)
            d = leaf.shape[-1]
            return P(batch_s, None, "model" if d % mp == 0 else None)
        if name in ("shift_tm", "shift_cm"):               # (L, B, d)
            return P(None, batch_s, "model")
        if name == "wkv":                                  # (L, B, H, dk, dv)
            h = leaf.shape[2]
            return P(None, batch_s, "model" if h % mp == 0 else None, None, None)
        if name == "conv":                                 # (L, B, k, conv_dim)
            return P(None, batch_s, None,
                     "model" if leaf.shape[-1] % mp == 0 else None)
        if name == "ssm":                                  # (L, B, nh, ds, hd)
            nh = leaf.shape[2]
            return P(None, batch_s, "model" if nh % mp == 0 else None, None, None)
        return P()

    return jax.tree_util.tree_map_with_path(spec_for, state_shape)


def batch_spec(mesh, extra_dims: int = 1) -> P:
    """Inputs (B, ...): batch over the composed data axes."""
    from repro.launch.mesh import batch_axes

    b = batch_axes(mesh)
    b = b if len(b) > 1 else b[0]
    return P(b, *(None,) * extra_dims)


def cache_spec(mesh, cfg: ModelConfig, batch: int, *, seq_axis_fallback=True) -> P:
    """KV cache (L, B, slots, KV, hd): shard batch if it divides, heads on
    ``model`` if divisible, else shard the sequence (slots) dim on ``model``
    (distributed-softmax decode)."""
    from repro.launch.mesh import batch_axes

    b = batch_axes(mesh)
    b = b if len(b) > 1 else b[0]
    bt = 1
    for a in (b if isinstance(b, tuple) else (b,)):
        bt *= mesh.shape[a]
    batch_s = b if batch % bt == 0 and batch >= bt else None
    kv_total = cfg.n_kv_heads
    if kv_total % mesh.shape["model"] == 0:
        return P(None, batch_s, None, "model", None)
    if batch_s is None and seq_axis_fallback:
        # batch=1 long-context: shard sequence over data AND model? keep it
        # on data only; model shards nothing here (attention is tiny vs FFN).
        return P(None, None, b, None, None)
    return P(None, batch_s, "model", None, None)
