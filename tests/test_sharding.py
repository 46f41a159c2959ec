"""Partition rules: coverage and divisibility over every arch's param tree,
plus batch/cache specs."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

import repro.configs as cfgs
from repro.models import SHAPES, build
from repro.sharding import specs as sspecs

AXES3 = ("pod", "data", "model")
MESH_SIZES = {"pod": 2, "data": 16, "model": 16}


def _iter_specs(tree, spec_tree):
    leaves = jax.tree_util.tree_leaves_with_path(tree)
    specs = jax.tree_util.tree_leaves(
        spec_tree, is_leaf=lambda x: isinstance(x, P))
    assert len(leaves) == len(specs)
    return [(sspecs.path_str(p), l, s) for (p, l), s in zip(leaves, specs)]


@pytest.mark.parametrize("arch", cfgs.ARCH_IDS)
def test_param_specs_rank_and_coverage(arch):
    cfg = cfgs.get(arch)
    api = build(cfg)
    tree = api.param_specs()
    spec_tree = sspecs.tree_partition_specs(tree, AXES3)
    n_sharded = 0
    for path, leaf, spec in _iter_specs(tree, spec_tree):
        assert len(spec) <= leaf.ndim, (path, spec, leaf.shape)
        if any(s is not None for s in spec):
            n_sharded += 1
    # the overwhelming majority of parameter BYTES must be sharded
    total = sum(l.size for _, l, _ in _iter_specs(tree, spec_tree))
    sharded = sum(
        l.size for _, l, s in _iter_specs(tree, spec_tree)
        if any(x is not None for x in s))
    assert sharded / total > 0.99, f"{arch}: only {sharded/total:.2%} sharded"


@pytest.mark.parametrize("arch", ["qwen3_1p7b", "llama4_maverick_400b_a17b",
                                  "jamba_1p5_large_398b"])
def test_param_specs_mostly_divisible(arch):
    """Sharded dims should be divisible by their mesh axes for the big
    tensors (uneven shards compile but waste memory via padding)."""
    cfg = cfgs.get(arch)
    api = build(cfg)
    tree = api.param_specs()
    spec_tree = sspecs.tree_partition_specs(tree, AXES3)
    bad_bytes = total = 0
    for path, leaf, spec in _iter_specs(tree, spec_tree):
        total += leaf.size
        for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * leaf.ndim):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            k = 1
            for a in axes:
                k *= MESH_SIZES[a]
            if dim % k:
                bad_bytes += leaf.size
                break
    assert bad_bytes / max(total, 1) < 0.02, f"{arch}: {bad_bytes/total:.2%} padded"


def test_batch_specs():
    b = {"tokens": jnp.zeros((8, 16), jnp.int32),
         "cache_index": jnp.zeros((), jnp.int32)}
    out = sspecs.batch_partition_specs(b, AXES3)
    assert out["tokens"] == P(("pod", "data"), None)
    assert out["cache_index"] == P()


def test_cache_specs_shard_batch_or_seq():
    cfg = cfgs.get("llama3p2_1b")
    api = build(cfg)
    cache = jax.eval_shape(lambda: api.make_caches(128, 1024))
    specs = sspecs.cache_partition_specs(cache, AXES3, global_batch=128,
                                         dp_size=32)
    flat = jax.tree_util.tree_leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert all(any(s is not None for s in sp) for sp in flat)
    # B=1 long-context: sequence must carry the sharding instead
    specs1 = sspecs.cache_partition_specs(cache, AXES3, global_batch=1,
                                          dp_size=32)
    flat1 = jax.tree_util.tree_leaves(specs1, is_leaf=lambda x: isinstance(x, P))
    assert any(sp[2] is not None for sp in flat1 if len(sp) >= 3)


def test_hints_noop_without_mesh_context():
    from repro.sharding.hints import shard_hint

    x = jnp.ones((4, 8, 16))
    assert shard_hint(x, "activations") is x


def test_hints_pin_outside_shard_map_and_step_aside_inside():
    """Inside a shard_map body the mesh axes are manual: a hint there has
    nothing to pin and must not raise (it used to be swallowed by a
    catch-all); outside, the constraint is applied."""
    from repro.launch.mesh import make_mesh
    from repro.sharding.hints import mesh_axes, shard_hint

    mesh = make_mesh((1, 1), ("data", "model"))
    spec = P("data", "model", None)

    def body(x):
        assert shard_hint(x, "activations") is x
        return x * 2

    @jax.jit
    def f(x):
        x = shard_hint(x, "activations")
        return jax.shard_map(body, mesh=mesh, in_specs=spec,
                             out_specs=spec)(x)

    with mesh, mesh_axes(mesh.axis_names):
        text = f.lower(jnp.ones((2, 8, 16))).as_text()
        out = f(jnp.ones((2, 8, 16)))
    assert "sharding_constraint" in text
    assert float(out.sum()) == 2 * 2 * 8 * 16
