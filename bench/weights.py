"""Weights made by the benchmark from the seed, in one jitted call.

The program supplies only the SHAPE of its parameter tree
(``jax.eval_shape`` of its own initialiser); every value is drawn here, on
the device, in the dtype it is served in. The reference reads these same
arrays by their names, so it never takes a number the program made.

Matrices are N(0, 1/fan_in); embedding tables N(0, 1); norm scales
1 + 0.1 N(0, 1), so a path that drops or misplaces a norm scale shows;
any other 1-D leaf is a bias (a router's per-expert score correction,
say), 0.1 N(0, 1), so a path that drops it shows.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A raw threefry key holding all 64 bits of ``seed`` (``PRNGKey``
    drops the high word when 64-bit types are off)."""
    s = int(seed) % (1 << 64)
    return jnp.asarray(np.array([s >> 32, s & 0xFFFFFFFF], np.uint32))


def _leaf(key, name: str, shape, dtype):
    if "norm" in name:
        x = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif "embed" in name:
        x = jax.random.normal(key, shape, jnp.float32)
    elif len(shape) == 1:
        x = 0.1 * jax.random.normal(key, shape, jnp.float32)
    else:
        x = jax.random.normal(key, shape, jnp.float32) * shape[-2] ** -0.5
    return x.astype(dtype)


def make_params(shapes, seed: int):
    """Values for every leaf of ``shapes`` (a pytree of
    ``ShapeDtypeStruct``), each from its own fold of the seed's key."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [jax.tree_util.keystr(p) for p, _ in flat]
    structs = [(tuple(s.shape), s.dtype) for _, s in flat]

    def build(key):
        return jax.tree_util.tree_unflatten(treedef, [
            _leaf(jax.random.fold_in(key, i), n, shape, dt)
            for i, (n, (shape, dt)) in enumerate(zip(names, structs))])

    return jax.jit(build)(seed_key(seed))
