"""Sharding-rule unit tests on abstract meshes (no devices needed):
divisibility handling, family coverage, and the state-spec table."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ARCHS, get_config
from repro.distributed.sharding import cache_spec, param_specs, state_specs
from repro.models import model as M

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH_MP = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def shapes_of(cfg):
    return jax.eval_shape(
        lambda: M.init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16))


@pytest.mark.parametrize("arch", ARCHS)
def test_param_specs_rank_and_divisibility(arch):
    cfg = get_config(arch)
    shapes = shapes_of(cfg)
    specs = param_specs(shapes, cfg, MESH)

    def check(path, leaf, spec):
        assert len(spec) <= len(leaf.shape), (path, leaf.shape, spec)
        for dim, s in zip(leaf.shape, tuple(spec) + (None,) * 8):
            if s is None:
                continue
            axes = (s,) if isinstance(s, str) else s
            total = 1
            for a in axes:
                total *= MESH.shape[a]
            assert dim % total == 0, (path, leaf.shape, spec)

    jax.tree_util.tree_map_with_path(
        lambda p, l, s: check(p, l, s), shapes, specs)


@pytest.mark.parametrize("arch", ["yi-6b", "qwen3-moe-30b-a3b", "rwkv6-7b"])
def test_param_specs_shard_the_big_tensors(arch):
    """Every >=2D tensor with a divisible dim must actually be sharded
    somewhere (no accidentally-replicated weight matrices)."""
    cfg = get_config(arch)
    shapes = shapes_of(cfg)
    specs = param_specs(shapes, cfg, MESH)
    leaves = jax.tree_util.tree_leaves_with_path(
        jax.tree.map(lambda s: s, shapes))
    spec_leaves = jax.tree_util.tree_leaves(specs, is_leaf=lambda x: isinstance(x, P))
    replicated_big = []
    for (path, leaf), spec in zip(leaves, spec_leaves):
        dims = sorted(leaf.shape)[-2:]
        # real weight matrices (>= 1M elements in the trailing matmul dims);
        # stacked norm scales / token-shift mixes are replicated by design
        if leaf.ndim >= 2 and dims[0] * dims[1] >= 1 << 20:
            if all(s is None for s in spec):
                replicated_big.append(jax.tree_util.keystr(path))
    assert not replicated_big, f"replicated: {replicated_big}"


def test_moe_experts_sharded_on_model():
    cfg = get_config("qwen3-moe-235b-a22b")
    shapes = shapes_of(cfg)
    specs = param_specs(shapes, cfg, MESH)
    wg = specs["layers"]["moe"]["w_gate"]
    assert wg[1] == "model"   # (L, E, d, d_e): experts on the tensor axis


def test_state_specs_cover_all_families():
    for arch in ARCHS:
        cfg = get_config(arch)
        state = jax.eval_shape(
            lambda cfg=cfg: M.init_decode_state(cfg, 128, 1024, jnp.bfloat16,
                                                num_frames=64))
        specs = state_specs(state, cfg, MESH, 128)
        for leaf, spec in zip(jax.tree.leaves(state),
                              jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))):
            for dim, s in zip(leaf.shape, tuple(spec) + (None,) * 8):
                if s is None:
                    continue
                axes = (s,) if isinstance(s, str) else s
                total = 1
                for a in axes:
                    total *= MESH.shape[a]
                assert dim % total == 0, (arch, leaf.shape, spec)


def test_cache_spec_batch1_falls_back_to_sequence():
    cfg = get_config("yi-6b")   # kv=4, not divisible by 16
    spec = cache_spec(MESH, cfg, batch=1)
    assert spec[2] is not None   # slots dim sharded
    spec_big = cache_spec(MESH, cfg, batch=128)
    assert spec_big[1] is not None   # batch sharded


def test_multipod_batch_axes_compose():
    cfg = get_config("yi-6b")
    shapes = shapes_of(cfg)
    specs = param_specs(shapes, cfg, MESH_MP)
    wq = specs["layers"]["attn"]["wq"]
    # FSDP dim carries the composed ("pod", "data") axes
    assert wq[1] == ("pod", "data")
