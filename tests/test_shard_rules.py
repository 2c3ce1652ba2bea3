"""Sharding rules: validity (divisibility) for every arch on the production
mesh shapes, using AbstractMesh (no fake devices needed in-process)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro import shard
from repro.launch.specs import cache_struct, input_specs, param_structs
from repro.nn.types import SHAPES, applicable_shapes, get_config, list_configs

MESH_SHAPES = {"16x16": ((16, 16), ("data", "model")),
               "2x16x16": ((2, 16, 16), ("pod", "data", "model"))}


@pytest.fixture
def mesh(request):
    """The production mesh named by the (indirect) parameter, built when the
    test runs rather than at import."""
    shape, axes = MESH_SHAPES[request.param]
    return AbstractMesh(shape, axes)


def _axis_size(mesh, axis):
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        return int(np.prod([mesh.shape[a] for a in axis]))
    return mesh.shape[axis]


def _check_tree(mesh, spec_tree, sds_tree):
    specs = jax.tree_util.tree_leaves(
        spec_tree, is_leaf=lambda x: isinstance(x, P))
    leaves = jax.tree_util.tree_leaves(sds_tree)
    assert len(specs) == len(leaves)
    for spec, leaf in zip(specs, leaves):
        assert len(spec) <= len(leaf.shape), (spec, leaf.shape)
        for dim, axis in zip(leaf.shape, tuple(spec)):
            size = _axis_size(mesh, axis)
            assert dim % size == 0, (spec, leaf.shape, axis)


@pytest.mark.parametrize("arch", list_configs())
@pytest.mark.parametrize("mesh", list(MESH_SHAPES), indirect=True)
def test_param_specs_divisible(arch, mesh):
    cfg = get_config(arch)
    p_sds = param_structs(cfg)
    _check_tree(mesh, shard.param_specs(mesh, p_sds), p_sds)


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "arctic-480b", "rwkv6-3b",
                                  "recurrentgemma-9b", "whisper-base"])
@pytest.mark.parametrize("mesh", ["16x16"], indirect=True)
def test_cache_and_batch_specs_divisible(arch, mesh):
    cfg = get_config(arch)
    for s in applicable_shapes(cfg):
        b_sds = input_specs(cfg, s)
        _check_tree(mesh, shard.batch_specs(mesh, b_sds), b_sds)
        if s.kind == "decode":
            c_sds = cache_struct(cfg, s)
            _check_tree(mesh, shard.cache_specs(mesh, c_sds), c_sds)


@pytest.mark.parametrize("mesh", ["16x16"], indirect=True)
def test_tp_sharding_covers_big_params(mesh):
    """The largest parameters must actually be sharded (not replicated) —
    arctic would not fit otherwise (DESIGN.md 4)."""
    cfg = get_config("arctic-480b")
    p_sds = param_structs(cfg)
    specs = shard.param_specs(mesh, p_sds)
    flat_sds = jax.tree_util.tree_leaves_with_path(p_sds)
    flat_spec = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    for (path, leaf), spec in zip(flat_sds, flat_spec):
        nbytes = leaf.size * leaf.dtype.itemsize
        if nbytes > 1 << 28:       # every leaf > 256MB must shard >= 16 ways
            ways = int(np.prod([_axis_size(mesh, a) for a in tuple(spec)]))
            assert ways >= 16, (path, leaf.shape, spec)


def test_applicable_shapes_skip_rules():
    """long_500k only for sub-quadratic archs (DESIGN.md 5)."""
    names = {c: [s.name for s in applicable_shapes(get_config(c))]
             for c in list_configs()}
    assert "long_500k" in names["rwkv6-3b"]
    assert "long_500k" in names["recurrentgemma-9b"]
    for dense in ("qwen2.5-3b", "arctic-480b", "llava-next-34b",
                  "whisper-base"):
        assert "long_500k" not in names[dense]
    # everything else runs all four shapes or three
    assert all(len(v) >= 3 for v in names.values())
