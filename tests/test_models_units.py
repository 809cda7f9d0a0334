"""Unit + property tests for model substrates: linear-scan equivalences,
MoE dispatch strategies, attention masks, RoPE, data pipeline, checkpointing."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis (CI installs it)")
st = pytest.importorskip("hypothesis.strategies")

from repro.checkpoint import checkpointer
from repro.configs import get_config
from repro.data.synthetic import DataConfig, SyntheticTokens
from repro.models.attention import attention_forward, init_attention
from repro.models.linear_scan import gla_chunked, gla_recurrent
from repro.models.moe import moe_mlp_onehot, moe_mlp_scatter, init_moe_mlp


@hypothesis.given(
    L=st.integers(4, 96),
    chunk=st.sampled_from([8, 16, 32]),
    inclusive=st.booleans(),
    strong=st.booleans(),
)
@hypothesis.settings(max_examples=12, deadline=None)
def test_gla_chunked_matches_recurrent(L, chunk, inclusive, strong):
    key = jax.random.PRNGKey(L * 7 + chunk)
    ks = jax.random.split(key, 4)
    B, H, dk, dv = 2, 2, 8, 12
    q = jax.random.normal(ks[0], (B, L, H, dk))
    k = jax.random.normal(ks[1], (B, L, H, dk))
    v = jax.random.normal(ks[2], (B, L, H, dv))
    scale = 25.0 if strong else 0.5
    ld = -jnp.abs(jax.random.normal(ks[3], (B, L, H, dk))) * scale
    o_ref, s_ref = gla_recurrent(q, k, v, ld, inclusive=inclusive)
    o, s = gla_chunked(q, k, v, ld, inclusive=inclusive, chunk=chunk)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=5e-4,
                               rtol=2e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), atol=5e-4,
                               rtol=2e-4)


def test_gla_scalar_decay_matches_broadcast():
    """SSD specialization: (B,L,H,1) decay == broadcasting it to dk."""
    key = jax.random.PRNGKey(5)
    ks = jax.random.split(key, 4)
    B, L, H, dk, dv = 2, 64, 3, 16, 32
    q = jax.random.normal(ks[0], (B, L, H, dk))
    k = jax.random.normal(ks[1], (B, L, H, dk))
    v = jax.random.normal(ks[2], (B, L, H, dv))
    ld1 = -jnp.abs(jax.random.normal(ks[3], (B, L, H, 1)))
    ld = jnp.broadcast_to(ld1, (B, L, H, dk))
    o1, s1 = gla_chunked(q, k, v, ld1, chunk=16)
    o2, s2 = gla_chunked(q, k, v, ld, chunk=16)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-4)
    o3, s3 = gla_recurrent(q, k, v, ld)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o3), atol=1e-4)


def test_gla_state_carry_composes():
    """Running two halves with carried state == running the whole sequence."""
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 4)
    B, L, H, dk, dv = 1, 64, 2, 8, 8
    q = jax.random.normal(ks[0], (B, L, H, dk))
    k = jax.random.normal(ks[1], (B, L, H, dk))
    v = jax.random.normal(ks[2], (B, L, H, dv))
    ld = -jnp.abs(jax.random.normal(ks[3], (B, L, H, dk)))
    o_full, s_full = gla_chunked(q, k, v, ld, chunk=16)
    o1, s1 = gla_chunked(q[:, :32], k[:, :32], v[:, :32], ld[:, :32], chunk=16)
    o2, s2 = gla_chunked(q[:, 32:], k[:, 32:], v[:, 32:], ld[:, 32:], s1, chunk=16)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([o1, o2], 1)),
                               np.asarray(o_full), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full), atol=1e-4)


def test_moe_scatter_matches_onehot():
    """The two dispatch strategies agree when nothing is dropped."""
    cfg = get_config("qwen3-moe-30b-a3b", smoke=True)
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, capacity_factor=8.0))
    p = init_moe_mlp(jax.random.PRNGKey(0), cfg)
    x = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    y1, aux1 = moe_mlp_scatter(p, x, cfg)
    y2, aux2 = moe_mlp_onehot(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)
    np.testing.assert_allclose(float(aux1), float(aux2), rtol=1e-5)


def test_moe_capacity_drops_tokens():
    """With capacity_factor << 1 the output must differ from no-drop (tokens
    actually get dropped) but stay finite."""
    cfg = get_config("qwen3-moe-30b-a3b", smoke=True)
    tight = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, capacity_factor=0.25))
    loose = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, capacity_factor=8.0))
    p = init_moe_mlp(jax.random.PRNGKey(0), cfg)
    x = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model))
    y_tight, _ = moe_mlp_scatter(p, x, tight)
    y_loose, _ = moe_mlp_scatter(p, x, loose)
    assert bool(jnp.isfinite(y_tight).all())
    assert float(jnp.max(jnp.abs(y_tight - y_loose))) > 1e-4


def test_moe_grads_flow_to_router():
    cfg = get_config("qwen3-moe-30b-a3b", smoke=True)
    p = init_moe_mlp(jax.random.PRNGKey(0), cfg)
    x = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (1, 16, cfg.d_model))

    def loss(p):
        y, aux = moe_mlp_scatter(p, x, cfg)
        return jnp.mean(y**2) + 0.01 * aux

    g = jax.grad(loss)(p)
    assert float(jnp.max(jnp.abs(g["router"]))) > 0.0


def test_causal_mask_blocks_future():
    """Perturbing future tokens must not change past outputs."""
    d, H, KV, hd = 64, 4, 2, 16
    p = init_attention(jax.random.PRNGKey(0), d, H, KV, hd)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, d))
    pos = jnp.arange(8)
    kw = dict(n_heads=H, n_kv=KV, head_dim=hd, rope_theta=1e4, causal=True)
    y1 = attention_forward(p, x, pos, **kw)
    x2 = x.at[:, 5:].add(100.0)
    y2 = attention_forward(p, x2, pos, **kw)
    np.testing.assert_allclose(np.asarray(y1[:, :5]), np.asarray(y2[:, :5]),
                               atol=1e-5)


def test_sliding_window_mask():
    """With window w, token t must ignore tokens older than t-w+1."""
    d, H, KV, hd = 64, 4, 2, 16
    p = init_attention(jax.random.PRNGKey(0), d, H, KV, hd)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 12, d))
    pos = jnp.arange(12)
    kw = dict(n_heads=H, n_kv=KV, head_dim=hd, rope_theta=1e4, causal=True,
              window=4)
    y1 = attention_forward(p, x, pos, **kw)
    x2 = x.at[:, 0:2].add(100.0)   # tokens 0-1 are outside window of t >= 6
    y2 = attention_forward(p, x2, pos, **kw)
    np.testing.assert_allclose(np.asarray(y1[:, 6:]), np.asarray(y2[:, 6:]),
                               atol=1e-5)


def test_synthetic_data_deterministic():
    cfg = get_config("yi-6b", smoke=True)
    d1 = SyntheticTokens(cfg, DataConfig(batch_size=2, seq_len=8, seed=3))
    d2 = SyntheticTokens(cfg, DataConfig(batch_size=2, seq_len=8, seed=3))
    np.testing.assert_array_equal(np.asarray(d1.batch(7)["tokens"]),
                                  np.asarray(d2.batch(7)["tokens"]))
    assert not np.array_equal(np.asarray(d1.batch(0)["tokens"]),
                              np.asarray(d1.batch(1)["tokens"]))


def test_checkpoint_roundtrip(tmp_path):
    from repro.models import model as M
    cfg = get_config("rwkv6-7b", smoke=True)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    path = os.path.join(tmp_path, "ckpt", "step_1.msgpack")
    checkpointer.save(path, params)
    zeros = jax.tree.map(jnp.zeros_like, params)
    restored = checkpointer.restore(path, zeros)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert checkpointer.latest_step(os.path.dirname(path)) == 1


def test_training_reduces_cfm_loss():
    """A few steps of the real trainer must reduce the CFM loss."""
    from repro.launch.train import train
    _, losses = train("yi-6b", smoke=True, steps=30, batch=8, seq=16,
                      lr=3e-3, log=lambda *_: None)
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) * 0.9, losses[:3] + losses[-3:]


def _unpinned(monkeypatch):
    """The projections as plain ``x @ w``, with no output layout pin."""
    from repro.models import attention
    monkeypatch.setattr(attention, "_project", lambda x, w: x @ w)


def _gqa_cfg(qk_norm, dtype="bfloat16"):
    cfg = get_config("yi-6b", smoke=True)
    assert cfg.n_heads > cfg.n_kv_heads > 1
    return dataclasses.replace(cfg, qk_norm=qk_norm, dtype=dtype)


@pytest.mark.parametrize("head", ["flow_velocity", "lm_forward"])
@pytest.mark.parametrize("qk_norm", [False, True])
def test_pinned_projections_bit_identical_to_unpinned(monkeypatch, head,
                                                      qk_norm):
    """A layout pin moves data, not arithmetic: same bits out."""
    from repro.models import model as M
    from repro.models.transformer import flow_velocity, lm_forward
    cfg = _gqa_cfg(qk_norm)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (3, 16), 0, cfg.vocab)
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 16, cfg.latent_dim))
    t = jnp.linspace(0.1, 0.9, 3)
    if head == "flow_velocity":
        def run(p):
            return flow_velocity(p, cfg, t, x, tokens)
    else:
        def run(p):
            return lm_forward(p, cfg, tokens)
    pinned = jax.jit(run)(params)
    _unpinned(monkeypatch)
    plain = jax.jit(lambda p: run(p))(params)   # new function: a new trace
    np.testing.assert_array_equal(np.asarray(pinned), np.asarray(plain))


def test_pinned_projections_cfm_grad_bit_identical_to_unpinned(monkeypatch):
    """The pin's transpose pins the cotangent the same way: same gradient.

    In float32 weights. With bf16 weights the CPU's weight-gradient dots see
    other operand layouts and round a few elements one or two bf16 ulps
    apart; the bf16 forward is bit-identical (the test above)."""
    from repro.core.schedulers import get_scheduler
    from repro.models import model as M
    cfg = _gqa_cfg(qk_norm=True, dtype="float32")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                          cfg.vocab)}
    sched = get_scheduler("fm_ot")

    def grad(p):
        return jax.grad(M.cfm_loss)(p, cfg, batch, jax.random.PRNGKey(3),
                                    sched)
    pinned = jax.jit(grad)(params)
    _unpinned(monkeypatch)
    plain = jax.jit(lambda p: grad(p))(params)  # new function: a new trace
    for a, b in zip(jax.tree.leaves(pinned), jax.tree.leaves(plain)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
