"""Plain reference of what the cells serve, written from the published
descriptions, imports nothing of the program.

What every flow configuration shares: a decoder stack as the backbone of
the paper's velocity field, with latents in through a linear projection
plus the conditioning token embeddings, a sinusoidal time embedding
through a two-layer SiLU MLP, the stack, a linear projection out;
classifier-free guidance mixes the conditional and the unconditional
field; the anytime nested Euler solver integrates it. The stack itself
(blocks and final norm) is the one the configuration names:
``"blocks": "<stack>"`` in ``configs/<name>.json`` is the module
``blocks/<stack>.py`` (``Model.stack``), built on ``linear``, ``rmsnorm``
and ``rotary`` here.

``mode`` picks the arithmetic:
  * ``"f32"``: every matrix product in float32 at ``highest`` precision,
    weights upcast layer by layer inside the stack's scan, so at most one
    layer's float32 copy is alive — the reference proper;
  * ``"fp8"``: the control, one step below the configuration's bfloat16:
    weights and inputs cast to float8 e4m3, scaled per output channel and
    per row.
Attention scores and softmax stay in float32 in both.

Parameters are read by name from the tree the benchmark made
(``weights.make_params``); ``c`` is a configuration dict from
``configs/*.json``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _f8(x, axis):
    """float8 e4m3 quantise-dequantise, scaled so each slice along
    ``axis`` spans the format's range (448)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def linear(x, w, mode: str):
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if mode == "fp8":
        x, w = _f8(x, -1), _f8(w, 0)
    elif mode != "f32":
        raise KeyError(mode)
    return jnp.matmul(x, w, precision=HI)


def rmsnorm(x, w, eps):
    x = x.astype(jnp.float32)
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def rotary(x, pos, theta):
    """x (..., S, H, hd) rotated by position: pairs (i, i + hd/2)."""
    hd = x.shape[-1]
    inv = theta ** (-np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


# -- flow ---------------------------------------------------------------------


def time_features(t, d):
    """Sinusoidal embedding of flow time t (scalar): cos then sin of
    1000 t at d/2 frequencies spaced geometrically from 1 to 1e-4."""
    half = d // 2
    f = jnp.exp(-math.log(10_000.0) * jnp.arange(half) / half)
    ang = 1000.0 * t * f
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)])


def velocity(params, stack, c, t, x, tokens, mode):
    """u_t(x) for latents x (B, S, latent); ``tokens`` (B, S) or None for
    the unconditional field; ``stack`` is the configuration's block
    module (``Model.stack``)."""
    f = params["flow"]
    h = linear(x, f["proj_in"], mode)
    if tokens is not None:
        h = h + params["embed"][tokens].astype(jnp.float32)
    e = time_features(t, c["d_model"])[None]
    e = linear(jax.nn.silu(linear(e, f["time_w1"], mode)), f["time_w2"], mode)
    h = h + e[:, None, :]
    h = stack.blocks(params, c, h, jnp.arange(x.shape[1]), mode)
    return linear(h, f["proj_out"], mode)


def guided(params, stack, c, t, x, tokens, scale, mode):
    uc = velocity(params, stack, c, t, x, tokens, mode)
    if scale == 0.0:
        return uc
    return (1.0 + scale) * uc - scale * velocity(params, stack, c, t, x, None,
                                                 mode)


def nested_euler(budgets):
    """The anytime solver the program is initialised with, from its
    definition: a non-monotone nested grid (each budget's first m times
    are i/m, new times appended as budgets grow), evaluation times clipped
    to [0.02, 0.98], intermediate rules x_{i+1} = x0 + s_i u_i with s_i
    the next grid time (1 after the last), and the budget-m exit
    x0 + mean(u_0 .. u_{m-1})."""
    grid = []
    for m in sorted(budgets):
        for i in range(m):
            if i / m not in grid:
                grid.append(i / m)
    nxt = grid[1:] + [1.0]
    return np.clip(grid, 0.02, 0.98), np.asarray(nxt)


def flow_sample(field, budgets, m, x0):
    """The budget-m sample from noise x0 (B, S, latent) under the nested
    Euler solver; ``field(t, x)`` is the guided velocity."""
    times, nxt = nested_euler(budgets)
    x, us = x0, []
    for i in range(m):
        u = field(np.float32(times[i]), x)
        us.append(u)
        x = x0 + np.float32(nxt[i]) * u
    if m == max(budgets):
        return x
    return x0 + sum(us) / m

