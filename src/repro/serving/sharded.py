"""Sharded serving execution: gateway batches on the production mesh.

Params are placed once via ``distributed.sharding.param_specs`` (Megatron +
FSDP rules — the same table training uses), and each gateway batch is split
along the composed data axes before dispatch, so the samplers' existing jit
programs lower to GSPMD collectives with no sampler code changes. When the
padded bucket does not divide the data-axis size the batch is replicated
instead (correct, just not data-parallel) — bucket sizes are powers of two,
so sizing ``max_batch`` to the data axis keeps every bucket divisible.

No mesh -> nothing here runs and serving stays single-device jit (the
``Gateway(mesh=None)`` default).
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


def _data_axes(mesh):
    from repro.launch.mesh import batch_axes

    axes = batch_axes(mesh)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return (axes if len(axes) > 1 else axes[0]), size


def data_axis_size(mesh) -> int:
    """Devices along the composed data axes: the fewest rows a batch
    needs to stay split."""
    return _data_axes(mesh)[1]


def shard_params(params, cfg, mesh):
    """Place a backbone param pytree on ``mesh`` per the serving/training
    sharding rules; returns the (now sharded) pytree."""
    from repro.distributed.sharding import param_specs

    specs = param_specs(params, cfg, mesh)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    return jax.device_put(params, shardings)


def shard_sampler(sampler, mesh):
    """Re-place a ``FlowSampler``/``AnytimeFlowSampler``'s params on the
    mesh, in place. Its jit'd programs recompile (once per budget/bucket)
    against the sharded layout on next call."""
    sampler.params = shard_params(sampler.params, sampler.cfg, mesh)
    return sampler


def place_decode_state(state, cfg, mesh):
    """Place a slot-batched decode state (dense ``KVCache``, ``PagedKVCache``,
    or recurrent state) on ``mesh`` per ``distributed.sharding.state_specs``
    — paged pools shard their KV heads on ``model`` while the block table
    and per-row positions stay replicated. The engine's write-masked step
    programs recompile once against the sharded layout."""
    from repro.distributed.sharding import state_specs

    batch = int(state.index.shape[0]) if state.index.ndim else 1
    specs = state_specs(state, cfg, mesh, batch)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    return jax.device_put(state, shardings)


def batch_placer(mesh):
    """A ``place(cond, x0) -> (cond, x0)`` callable sharding batch arrays
    along the data axes (leading dim), replicating when indivisible."""
    axes, size = _data_axes(mesh)

    def place_one(x):
        spec_b = axes if x.shape[0] % size == 0 else None
        sharding = NamedSharding(mesh, P(spec_b, *(None,) * (x.ndim - 1)))
        return jax.device_put(x, sharding)

    def place(cond, x0):
        x0 = place_one(x0)
        if cond is not None:
            cond = {k: place_one(v) if hasattr(v, "ndim") and v.ndim else v
                    for k, v in cond.items()}
        return cond, x0

    return place


def tier_placer(mesh, ladder):
    """``batch_placer`` specialised to a ``ShapeLadder``: a tier ladder
    makes the set of dispatch shapes finite and known up front — every
    batch is (bucket, rung, *tail) for a configured rung — so the
    ``NamedSharding`` for each shape is built once and cached, and the
    per-dispatch cost is a dict lookup instead of a spec construction.
    The cache admits only shapes whose position axis is a configured
    rung, so it is bounded by (buckets x rungs) regardless of traffic;
    off-ladder shapes (untiered ndim<2 samples sharing the gateway)
    place correctly but uncached, like ``batch_placer``."""
    axes, size = _data_axes(mesh)
    rungs = frozenset(ladder.rungs)
    cache: dict = {}

    def sharding_for(shape):
        spec_b = axes if shape[0] % size == 0 else None
        return NamedSharding(mesh, P(spec_b, *(None,) * (len(shape) - 1)))

    def place_one(x):
        shape = tuple(x.shape)
        if len(shape) >= 2 and shape[1] in rungs:
            s = cache.get(shape)
            if s is None:
                s = cache[shape] = sharding_for(shape)
            return jax.device_put(x, s)
        return jax.device_put(x, sharding_for(shape))

    def place(cond, x0):
        x0 = place_one(x0)
        if cond is not None:
            cond = {k: place_one(v) if hasattr(v, "ndim") and v.ndim else v
                    for k, v in cond.items()}
        return cond, x0

    return place


def carry_placer(mesh):
    """A ``place(carry) -> carry`` callable re-placing the continuous
    engine's slot-batched carry arrays after a join scatters new rows:
    ``x0``/``x`` along the data axes on the leading (slot) dim, ``U`` on its
    slot dim (axis 1), replicating when the slot count does not divide the
    data-axis size — size ``max_slots`` to the data axis to stay split."""
    axes, size = _data_axes(mesh)

    def place_axis(x, dim):
        spec = [None] * x.ndim
        if x.shape[dim] % size == 0:
            spec[dim] = axes
        return jax.device_put(x, NamedSharding(mesh, P(*spec)))

    def place(carry):
        return carry._replace(x0=place_axis(carry.x0, 0),
                              U=place_axis(carry.U, 1),
                              x=place_axis(carry.x, 0))

    return place


def serving_mesh(name: str):
    """CLI mesh selection: 'none' -> None (single-device jit), 'host' ->
    every local device on the ``model`` axis, 'production'/'multipod' ->
    ``launch.mesh`` shapes. Raises when the host lacks the devices for the
    mesh asked for; it never falls back to fewer devices."""
    if name in (None, "none"):
        return None
    from repro.launch import mesh as mesh_mod

    if name == "host":
        return mesh_mod.make_host_mesh()
    if name == "production":
        return mesh_mod.make_production_mesh()
    if name == "multipod":
        return mesh_mod.make_production_mesh(multi_pod=True)
    raise ValueError(f"unknown mesh {name!r}; "
                     "choose none|host|production|multipod")
