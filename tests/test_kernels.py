"""Pallas kernel validation (interpret=True on CPU; TPU is the target).

Each kernel is swept over shapes/dtypes and asserted allclose against its
pure-jnp ref.py oracle, plus integration checks (ns_update inside Algorithm 1,
flash attention vs the model's attention path).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.paged_attention import paged_attention
from repro.kernels.flash_attention.ref import attention_ref, paged_attention_ref
from repro.kernels.gla_scan.gla_scan import gla_scan
from repro.kernels.gla_scan.ref import gla_ref
from repro.kernels.ns_update.ns_update import ns_update_nd
from repro.kernels.ns_update.ops import make_update_fn
from repro.kernels.ns_update.ref import ns_update_ref

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


# ---------------------------------------------------------------------------
# ns_update
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,B,D", [(4, 8, 512), (8, 2, 1024), (16, 4, 384),
                                   (20, 1, 128)])
def test_ns_update_sweep(n, B, D, dtype):
    key = jax.random.PRNGKey(n * 1000 + B + D)
    ks = jax.random.split(key, 4)
    x0 = jax.random.normal(ks[0], (B, D), dtype)
    u = jax.random.normal(ks[1], (n, B, D), dtype)
    a = jax.random.normal(ks[2], ())
    w = jax.random.normal(ks[3], (n,))
    out = ns_update_nd(x0, u, a, w, interpret=True)
    ref = ns_update_ref(x0, u, a, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=TOL[dtype] * n, rtol=TOL[dtype])


def test_ns_update_3d_shapes():
    """Latent-sequence shapes (B, S, C) as used by the flow sampler."""
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 4)
    x0 = jax.random.normal(ks[0], (2, 24, 16))        # D = 384, padded to 512
    u = jax.random.normal(ks[1], (8, 2, 24, 16))
    a = jax.random.normal(ks[2], ())
    w = jax.random.normal(ks[3], (8,))
    out = ns_update_nd(x0, u, a, w, interpret=True)
    ref = ns_update_ref(x0, u, a, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_ns_update_inside_algorithm1():
    """Algorithm 1 with the fused kernel == Algorithm 1 with jnp updates."""
    from repro.core import ns_solver, schedulers, toy
    from repro.core.bns import solver_to_ns

    sched = schedulers.fm_ot()
    field = toy.mixture_field(sched, toy.two_moons_means(),
                              jnp.full((16,), 0.15), jnp.ones((16,)))
    x0 = jax.random.normal(jax.random.PRNGKey(0), (4, 2))
    ns = solver_to_ns("midpoint", 8, field)
    base = ns_solver.ns_sample(ns, field.fn, x0)
    fused = ns_solver.ns_sample(ns, field.fn, x0,
                                update_fn=make_update_fn(interpret=True))
    np.testing.assert_allclose(np.asarray(fused), np.asarray(base), atol=1e-4)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KV,L,hd,causal", [
    (1, 4, 2, 256, 64, True),
    (2, 8, 8, 128, 128, True),
    (1, 4, 1, 256, 64, True),      # extreme GQA
    (1, 2, 2, 128, 128, False),    # bidirectional (encoder)
])
def test_flash_attention_sweep(B, H, KV, L, hd, causal, dtype):
    key = jax.random.PRNGKey(B + H + L)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, L, hd), dtype)
    k = jax.random.normal(ks[1], (B, KV, L, hd), dtype)
    v = jax.random.normal(ks[2], (B, KV, L, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, bq=64, bk=64, interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=TOL[dtype] * 4, rtol=TOL[dtype] * 4)


def test_flash_attention_matches_model_attention():
    """Kernel output == the model's einsum attention (same math, no RoPE)."""
    from repro.models.attention import _grouped_attend
    B, H, KV, L, hd = 1, 4, 2, 128, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, L, H, hd))
    k = jax.random.normal(ks[1], (B, L, KV, hd))
    v = jax.random.normal(ks[2], (B, L, KV, hd))
    mask = jnp.broadcast_to(jnp.tril(jnp.ones((L, L), bool)), (B, L, L))
    ref = _grouped_attend(q.reshape(B, L, KV, H // KV, hd), k, v, mask)
    ref = ref.reshape(B, L, H, hd).transpose(0, 2, 1, 3)
    out = flash_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                          v.transpose(0, 2, 1, 3), causal=True, bq=64, bk=64,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


# ---------------------------------------------------------------------------
# paged_attention (decode step over a paged KV cache)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,KV,G,hd,ps,nb", [
    (2, 2, 2, 64, 8, 4),
    (3, 1, 4, 32, 16, 2),      # extreme GQA, two blocks
    (1, 4, 1, 64, 8, 3),       # MQA-free per-head pages
])
def test_paged_attention_sweep(B, KV, G, hd, ps, nb, dtype):
    """Kernel == dense-gather oracle over a shuffled page pool with ragged
    per-row lengths (short rows skip whole pages via the prefetched
    scalars)."""
    key = jax.random.PRNGKey(B * 7 + nb)
    ks = jax.random.split(key, 4)
    num_pages = 1 + B * nb                   # page 0 = reserved trash page
    q = jax.random.normal(ks[0], (B, KV, G, hd), dtype)
    k_pages = jax.random.normal(ks[1], (num_pages, KV, ps, hd), dtype)
    v_pages = jax.random.normal(ks[2], (num_pages, KV, ps, hd), dtype)
    # each row owns nb distinct pages, in shuffled (non-contiguous) order
    perm = jax.random.permutation(ks[3], num_pages - 1)[:B * nb] + 1
    block_table = perm.reshape(B, nb).astype(jnp.int32)
    lengths = jnp.asarray([(i * ps + i + 1) % (nb * ps) + 1
                           for i in range(B)], jnp.int32)
    out = paged_attention(q, k_pages, v_pages, block_table, lengths,
                          interpret=True)
    ref = paged_attention_ref(q, k_pages, v_pages, block_table, lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=TOL[dtype] * 4, rtol=TOL[dtype] * 4)


def test_paged_attention_ignores_positions_past_length():
    """Garbage in a row's own pages past its length (the overwrite-invariant
    cells) must not leak into the output."""
    B, KV, G, hd, ps, nb = 1, 2, 2, 32, 8, 2
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, KV, G, hd))
    k_pages = jax.random.normal(ks[1], (1 + nb, KV, ps, hd))
    v_pages = jax.random.normal(ks[2], (1 + nb, KV, ps, hd))
    table = jnp.asarray([[1, 2]], jnp.int32)
    lengths = jnp.asarray([5], jnp.int32)
    base = paged_attention(q, k_pages, v_pages, table, lengths, interpret=True)
    poisoned_k = k_pages.at[1, :, 5:].set(1e4).at[2].set(-1e4)
    poisoned_v = v_pages.at[1, :, 5:].set(1e4).at[2].set(-1e4)
    out = paged_attention(q, poisoned_k, poisoned_v, table, lengths,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(base), atol=1e-5)


# ---------------------------------------------------------------------------
# gla_scan (RWKV6 / Mamba2 recurrence)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("L,chunk,inclusive,dk,dv", [
    (128, 32, True, 64, 64),     # mamba2-style (dk=d_state, dv=head_dim)
    (128, 32, False, 64, 64),    # rwkv6-style exclusive
    (96, 16, False, 32, 48),     # ragged head dims
    (64, 64, True, 16, 128),     # single chunk
])
def test_gla_scan_sweep(L, chunk, inclusive, dk, dv, dtype):
    key = jax.random.PRNGKey(L + chunk)
    ks = jax.random.split(key, 4)
    B, H = 2, 3
    q = jax.random.normal(ks[0], (B, L, H, dk), dtype)
    k = jax.random.normal(ks[1], (B, L, H, dk), dtype)
    v = jax.random.normal(ks[2], (B, L, H, dv), dtype)
    ld = -jnp.abs(jax.random.normal(ks[3], (B, L, H, dk))) * 0.5
    o, s = gla_scan(q, k, v, ld, inclusive=inclusive, chunk=chunk,
                    interpret=True)
    o_ref, s_ref = gla_ref(q, k, v, ld.astype(dtype), inclusive=inclusive)
    tol = TOL[dtype] * 20
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32), atol=tol,
                               rtol=TOL[dtype])
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), atol=tol,
                               rtol=TOL[dtype])


def test_gla_scan_strong_decay_stable():
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 4)
    B, L, H, dk, dv = 1, 128, 2, 16, 16
    q = jax.random.normal(ks[0], (B, L, H, dk))
    k = jax.random.normal(ks[1], (B, L, H, dk))
    v = jax.random.normal(ks[2], (B, L, H, dv))
    ld = -jnp.abs(jax.random.normal(ks[3], (B, L, H, dk))) * 30.0
    o, s = gla_scan(q, k, v, ld, inclusive=False, chunk=32, interpret=True)
    o_ref, s_ref = gla_ref(q, k, v, ld, inclusive=False)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(s).all())
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=1e-3)
