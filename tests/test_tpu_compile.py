"""Compile rehearsal for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret mode accepts (block shapes off the
(8, 128) tiling, VMEM overruns, programs too large for HBM), so the kernels
of the serving path and one whole step of each engine are compiled here at
yi-6b's published widths (depth cut to 2 for the whole steps). Nothing
runs: these say nothing about results or speed.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every xdist worker
imports this file.
"""
import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.parametrization import VelocityField
from repro.core.schedulers import get_scheduler
from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.paged_attention import paged_attention
from repro.kernels.ns_update.ns_update import ns_update_nd
from repro.kernels.ns_update.ops import make_update_fn
from repro.models import model as M
from repro.core.anytime import init_anytime
from repro.serving.engine import AnytimeFlowSampler, DecodeEngine, FlowSampler
from repro.solvers.registry import build_ns

HBM_BYTES = 16 * 2**30      # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip is written to the persistent
    # cache but cannot be read back without one
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _specs(sharding, tree):
    return jax.tree.map(lambda x: _spec(sharding, x.shape, x.dtype), tree)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _yi6b_depth2():
    return dataclasses.replace(get_config("yi-6b"), n_layers=2)


@pytest.mark.parametrize("batch", [1, 8, 12])
def test_ns_update_compiles_at_serving_bucket(one_chip, batch):
    n, shape = 8, (16, 64)          # 16 latent tokens of yi-6b's latent_dim
    _compile(lambda x0, u, a, w: ns_update_nd(x0, u, a, w, interpret=False),
             _spec(one_chip, (batch,) + shape),
             _spec(one_chip, (n, batch) + shape),
             _spec(one_chip, ()), _spec(one_chip, (n,)))


@pytest.mark.parametrize("seq", [256, 1024])
def test_flash_attention_compiles_at_yi6b_widths(one_chip, seq):
    B, H, KV, hd = 1, 32, 4, 128
    _compile(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                             interpret=False),
             _spec(one_chip, (B, H, seq, hd), jnp.bfloat16),
             _spec(one_chip, (B, KV, seq, hd), jnp.bfloat16),
             _spec(one_chip, (B, KV, seq, hd), jnp.bfloat16))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_compiles_at_yi6b_widths(one_chip, dtype):
    B, KV, G, hd, ps, nb = 8, 4, 8, 128, 16, 8
    pool = (1 + B * nb, KV, ps, hd)
    _compile(lambda q, k, v, bt, ln: paged_attention(q, k, v, bt, ln,
                                                     interpret=False),
             _spec(one_chip, (B, KV, G, hd), dtype),
             _spec(one_chip, pool, dtype), _spec(one_chip, pool, dtype),
             _spec(one_chip, (B, nb), jnp.int32),
             _spec(one_chip, (B,), jnp.int32))


def test_flow_step_compiles_at_yi6b_widths(one_chip):
    """FlowSampler's serving program: 8 NFE through the backbone with the
    Pallas NS update, 8 requests x 16 latent tokens."""
    cfg = _yi6b_depth2()
    sched = get_scheduler("fm_ot")
    params = jax.eval_shape(lambda k: M.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    solver = build_ns("euler", 8, VelocityField(fn=None, scheduler=sched))
    sampler = FlowSampler(params=None, cfg=cfg, sched=sched, solver=solver,
                          update_fn=make_update_fn(use_kernel=True,
                                                   interpret=False))
    compiled = sampler._sample.lower(
        _specs(one_chip, params), _specs(one_chip, solver),
        {"tokens": _spec(one_chip, (8, 16), jnp.int32)},
        _spec(one_chip, (8, 16, cfg.latent_dim))).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("width", [4, 16])
def test_anytime_leg_compiles_at_yi6b_widths(one_chip, width):
    """A guided trajectory leg 4..8 over a 16-slot carry of 64 latent
    positions, narrowed to ``width`` live rows (16: the full program)."""
    cfg = _yi6b_depth2()
    budgets = (4, 8, 16)
    sampler = AnytimeFlowSampler(params=None, cfg=cfg,
                                 sched=get_scheduler("fm_ot"),
                                 anytime=init_anytime(None, budgets),
                                 budgets=budgets, cfg_scale=1.5)
    params = jax.eval_shape(lambda k: M.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    slots, S, L = 16, 64, cfg.latent_dim
    rows = () if width == slots else (_spec(one_chip, (width,), jnp.int32),)
    compiled = sampler._leg(4, 8).lower(
        _specs(one_chip, params),
        {"tokens": _spec(one_chip, (slots, S), jnp.int32)},
        _spec(one_chip, (slots, S, L)), _spec(one_chip, (16, slots, S, L)),
        _spec(one_chip, (slots, S, L)), *rows).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


def test_paged_decode_step_compiles_at_yi6b_widths(one_chip, monkeypatch):
    """DecodeEngine's slot step over a paged pool through the Pallas
    paged-attention kernel (4 slots x 128 positions, page 16)."""
    import repro.kernels.flash_attention.ops as attention_ops

    # the engine picks interpret mode from the backend, which is the CPU here
    monkeypatch.setattr(attention_ops, "interpret_mode", lambda: False)
    cfg = _yi6b_depth2()
    params = jax.eval_shape(lambda k: M.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    engine = DecodeEngine(params=None, cfg=cfg, page_size=16,
                          paged_kernel=True)
    state = jax.eval_shape(lambda: engine.init_slot_state(4, 128))
    compiled = engine._step_slots.lower(
        _specs(one_chip, params), _spec(one_chip, (4,), jnp.int32),
        _specs(one_chip, state), _spec(one_chip, (4,), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _weight_relayouts(hlo: str, layers) -> tuple[list[str], list[str]]:
    """Instructions of ``hlo`` that copy a whole stacked layer weight, and
    dynamic-slice fusions that yield one layer's slice of one (in bf16)."""
    weights = [w for w in jax.tree.leaves(layers) if w.ndim == 3]
    stacks = {"bf16[%s]" % ",".join(map(str, w.shape)) for w in weights}
    per_layer = {math.prod(w.shape[1:]) for w in weights}
    copies, slices = [], []
    for line in hlo.splitlines():
        m = re.match(r"\s*%(\S+) = (bf16\[([\d,]*)\])\S* (\S+)\(", line)
        if not m:
            continue
        name, shape, dims, op = m.groups()
        if op == "copy" and shape in stacks:
            copies.append(line.strip())
        if (op == "fusion" and "dynamic-slice" in name
                and math.prod(int(d) for d in dims.split(",")) in per_layer):
            slices.append(line.strip())
    return copies, slices


@pytest.mark.parametrize("program", ["flush-8x32", "flush-guided-16x64",
                                     "leg-0-4-8x32"])
def test_flow_program_reads_stacked_weights_in_place(one_chip, program):
    """The flow programs of both benchmark cells at yi-6b widths read the
    stacked layer weights as stored: no per-call relayout of a whole stack
    and no per-layer weight slice in the layer scan (the attention
    projections pin their outputs, ``models.attention._project``)."""
    cfg = _yi6b_depth2()
    params = jax.eval_shape(lambda k: M.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    guided = program == "flush-guided-16x64"
    budgets = (4, 8, 16) if guided else (4,)
    sampler = AnytimeFlowSampler(params=None, cfg=cfg,
                                 sched=get_scheduler("fm_ot"),
                                 anytime=init_anytime(None, budgets),
                                 budgets=budgets,
                                 cfg_scale=1.5 if guided else 0.0)
    B, S = (16, 64) if guided else (8, 32)
    L = cfg.latent_dim
    args = (_specs(one_chip, params),
            {"tokens": _spec(one_chip, (B, S), jnp.int32)},
            _spec(one_chip, (B, S, L)))
    if program.startswith("leg"):
        fn = sampler._leg(0, 4)
        args += (_spec(one_chip, (max(budgets), B, S, L)),
                 _spec(one_chip, (B, S, L)))
    else:
        fn = sampler._at_budget(4)
    compiled = fn.lower(*args).compile()
    copies, slices = _weight_relayouts(compiled.as_text(), params["layers"])
    assert not copies, copies
    assert not slices, slices
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


def test_pinned_prefill_no_worse_than_unpinned(one_chip, monkeypatch):
    """At a 4,096-token prefill, past the token count where relaying the
    activations outweighs relaying the weights, the pinned projections
    still cost no more temp memory than plain ``x @ w`` and relayout no
    weight stack: the pin needs no switch on the token count."""
    from repro.models import attention
    from repro.models.transformer import lm_forward

    cfg = _yi6b_depth2()
    params = jax.eval_shape(lambda k: M.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    args = (_specs(one_chip, params), _spec(one_chip, (1, 4096), jnp.int32))

    def temp_and_copies():
        compiled = jax.jit(
            lambda p, t: lm_forward(p, cfg, t, last_only=True)
        ).lower(*args).compile()
        copies, _ = _weight_relayouts(compiled.as_text(), params["layers"])
        return compiled.memory_analysis().temp_size_in_bytes, copies

    pinned, pinned_copies = temp_and_copies()
    monkeypatch.setattr(attention, "_project", lambda x, w: x @ w)
    plain, _ = temp_and_copies()
    assert not pinned_copies, pinned_copies
    assert pinned <= plain
