"""The llama-style dense block stack, written from the published
descriptions (Yi, arXiv:2403.04652; LLaMA, arXiv:2302.13971): RMSNorm
before each sub-layer, rotary positions on the two halves of each head,
causal grouped-query attention, a SwiGLU MLP, residual adds, and a final
RMSNorm after the last block.

``blocks`` is the reference's stack (``reference.velocity`` runs it);
``position_params``, ``causal_attn_flops`` and ``matrix_bytes`` are the
least work of the same stack, which ``work.py`` and the readers take;
``KERNELS`` names the program's device kernels for the trace reduction.
``c`` is a configuration dict (``n_layers``, ``d_model``, ``n_heads``,
``n_kv_heads``, ``head_dim``, ``d_ff``, ``rope_theta``, ``norm_eps``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference import HI, linear, rmsnorm, rotary
from bench.work import param_bytes

# device kernels of the stack, by substrings of their operations' names:
# the program runs yi-6b's blocks as XLA fusions, so none
KERNELS: dict = {}


def attention(p, x, pos, c, mode):
    """Causal GQA over one batch of sequences x (B, S, d)."""
    B, S, _ = x.shape
    H, KV, hd = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    q = linear(x, p["wq"], mode).reshape(B, S, H, hd)
    k = linear(x, p["wk"], mode).reshape(B, S, KV, hd)
    v = linear(x, p["wv"], mode).reshape(B, S, KV, hd)
    q = rotary(q, pos, c["rope_theta"])
    k = rotary(k, pos, c["rope_theta"])
    # query head h reads key/value head h // (H / KV)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) / math.sqrt(hd)
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v,
                   precision=HI)
    return linear(o.reshape(B, S, H * hd), p["wo"], mode)


def blocks(params, c, h, pos, mode):
    """The stacked blocks (scanned) and the final norm."""
    eps = c["norm_eps"]

    def body(h, lp):
        a = attention(lp["attn"], rmsnorm(h, lp["norm1"], eps), pos, c, mode)
        h = h + a
        m = rmsnorm(h, lp["norm2"], eps)
        g = linear(m, lp["mlp"]["w_gate"], mode)
        u = linear(m, lp["mlp"]["w_up"], mode)
        h = h + linear(jax.nn.silu(g) * u, lp["mlp"]["w_down"], mode)
        return h, None

    h, _ = jax.lax.scan(body, h.astype(jnp.float32), params["layers"])
    return rmsnorm(h, params["final_norm"], eps)


# -- the least work of the stack -----------------------------------------------


def attn_params(c: dict) -> int:
    d, h, kv, hd = c["d_model"], c["n_heads"], c["n_kv_heads"], c["head_dim"]
    return d * h * hd + 2 * d * kv * hd + h * hd * d


def mlp_params(c: dict) -> int:
    return 3 * c["d_model"] * c["d_ff"]


def layer_params(c: dict) -> int:
    """Matrix parameters of one dense block (norm scales are O(d))."""
    return attn_params(c) + mlp_params(c)


def position_params(c: dict) -> int:
    """Matrix parameters one position's forward multiplies, over all the
    blocks: every block's, since the block is dense."""
    return c["n_layers"] * layer_params(c)


def causal_attn_flops(c: dict, new: int, past: int) -> float:
    """Score and value products of ``new`` queries that follow ``past``
    cached positions, each seeing itself and what precedes it: query i
    attends to ``past + i + 1`` keys, twice (QK^T and PV), over all
    layers and heads."""
    keys = new * past + new * (new + 1) / 2
    return 4.0 * c["n_layers"] * c["n_heads"] * c["head_dim"] * keys


def matrix_bytes(c: dict, tokens: int) -> int:
    """Bytes of the blocks' matrices a forward over ``tokens`` tokens reads
    at least, in the served dtype: every matrix once, whatever the token
    count (norm scales are left out, which only lowers the floor)."""
    return param_bytes(c) * position_params(c)
