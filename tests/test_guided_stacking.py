"""Classifier-free guidance in one backbone pass: where a family's two
branches differ only by the additive token embedding and its hidden
function treats rows independently (dense, ssm, hybrid), the guided field
stacks them on the batch axis and runs the backbone once over 2B rows;
vlm, encdec and moe keep two passes. The gateway counts the passes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.anytime import init_anytime
from repro.core.schedulers import fm_ot
from repro.data.synthetic import DataConfig, SyntheticTokens
from repro.models import model as M
from repro.serving import AnytimeFlowSampler, Gateway, Request
from repro.serving.engine import FlowSampler
from repro.serving.toy import FakeClock

W = 1.5
S = 8


def _model(arch):
    cfg = get_config(arch, smoke=True)
    return cfg, M.init_params(jax.random.PRNGKey(0), cfg)


def _batch(cfg, B):
    return SyntheticTokens(cfg, DataConfig(batch_size=B, seq_len=S)).batch(0)


def _t(B, per_row):
    if per_row:
        return jnp.linspace(0.1, 0.9, B)
    return jnp.float32(0.3)


def _two_calls(params, cfg, t, x, batch):
    return ((1.0 + W) * M.velocity(params, cfg, t, x, batch)
            - W * M.velocity(params, cfg, t, x, None))


@pytest.mark.parametrize("per_row", [False, True], ids=["t-scalar", "t-rows"])
@pytest.mark.parametrize("B", [1, 3])
@pytest.mark.parametrize("arch", ["yi-6b", "rwkv6-7b", "zamba2-2.7b"])
def test_stacked_guidance_matches_two_calls(arch, B, per_row):
    """One pass over 2B rows gives ``(1 + w) u_cond - w u_uncond`` of two
    separate calls, to float32 round-off (only the matmul batch each row
    rides in changes), for a scalar and a per-row ``t``."""
    cfg, params = _model(arch)
    assert M.passes_per_step(cfg, W) == 1
    batch = _batch(cfg, B)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.latent_dim))
    t = _t(B, per_row)
    got = M.velocity_field(params, cfg, fm_ot(), batch, cfg_scale=W).fn(t, x)
    want = _two_calls(params, cfg, t, x, batch)
    assert got.shape == want.shape == x.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("arch", ["internvl2-26b", "qwen3-moe-30b-a3b"])
def test_two_pass_families_are_unchanged(arch):
    """vlm (its conditioning prepends patches) and moe (expert capacity
    couples batch mates) keep the two separate calls: the guided field is
    exactly the two-call combination."""
    cfg, params = _model(arch)
    batch = _batch(cfg, 2)
    if cfg.family == "vlm":
        assert "patches" in batch
    x = jax.random.normal(jax.random.PRNGKey(1), (2, S, cfg.latent_dim))
    t = jnp.float32(0.3)
    got = M.velocity_field(params, cfg, fm_ot(), batch, cfg_scale=W).fn(t, x)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(_two_calls(params, cfg, t, x, batch)))


@pytest.mark.parametrize("arch,scale,passes", [
    ("yi-6b", W, 1), ("yi-6b", 0.0, 1), ("rwkv6-7b", W, 1),
    ("zamba2-2.7b", W, 1), ("internvl2-26b", W, 2),
    ("internvl2-26b", 0.0, 1), ("qwen3-moe-30b-a3b", W, 2),
    ("whisper-medium", W, 2)])
def test_samplers_state_passes_per_step(arch, scale, passes):
    cfg = get_config(arch, smoke=True)
    anytime = AnytimeFlowSampler(params=None, cfg=cfg, sched=fm_ot(),
                                 anytime=init_anytime(None, (2, 4)),
                                 budgets=(2, 4), cfg_scale=scale)
    fixed = FlowSampler(params=None, cfg=cfg, sched=fm_ot(), solver=None,
                        cfg_scale=scale)
    assert anytime.passes_per_step == fixed.passes_per_step == passes


@pytest.mark.parametrize("arch,scale,passes", [
    ("yi-6b", W, 1), ("yi-6b", 0.0, 1), ("internvl2-26b", W, 2)])
def test_gateway_counts_backbone_passes(arch, scale, passes):
    """``backbone_passes`` is the NFE steps ``forwards_by_rows`` counts
    times the sampler's passes per step."""
    cfg, params = _model(arch)
    sampler = AnytimeFlowSampler(params=params, cfg=cfg, sched=fm_ot(),
                                 anytime=init_anytime(None, (2, 4)),
                                 budgets=(2, 4), cfg_scale=scale)
    gw = Gateway(sampler, max_batch=2, max_wait_ms=10.0,
                 mixed_budget_policy="always", clock=FakeClock())
    futs = [gw.submit(Request(
        tokens=jnp.full((S,), i + 1, jnp.int32), budget=b,
        x0=jax.random.normal(jax.random.PRNGKey(i), (S, cfg.latent_dim))))
        for i, b in enumerate((2, 4))]
    gw.pump(force=True)
    assert all(f.done() for f in futs)
    snap = gw.metrics.snapshot()
    steps = sum(v for k, v in snap.items()
                if k.startswith("forwards_by_rows{"))
    assert steps == 4
    assert snap["backbone_passes"] == passes * steps
