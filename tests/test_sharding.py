"""Sharding-rule tests: every spec divides its dim, spec trees are congruent
with parameter trees for all 10 archs, and a tiny-mesh end-to-end jit with
the production rules runs on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, SHAPES, get_config, get_smoke_config, input_specs
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.parallel import sharding as shd


class _FakeMesh:
    """Shape-only stand-in so full-config spec checks don't need 256 devices."""

    def __init__(self, shape: dict):
        self.shape = shape
        self.axis_names = tuple(shape.keys())


PROD = _FakeMesh({"data": 16, "model": 16})
PROD_MP = _FakeMesh({"pod": 2, "data": 16, "model": 16})


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("mesh", [PROD, PROD_MP], ids=["single", "multi"])
def test_param_specs_divide(arch, mesh):
    cfg = get_config(arch)
    model = build_model(cfg)
    abstract = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    rules = shd.make_rules(cfg, mesh)
    specs = shd.param_specs(cfg, mesh, abstract, rules)
    flat_p = jax.tree.leaves(abstract)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_p) == len(flat_s)
    for leaf, spec in zip(flat_p, flat_s):
        assert len(spec) <= leaf.ndim
        for dim, axes in zip(leaf.shape, tuple(spec)):
            size = 1
            if axes is not None:
                for a in (axes if isinstance(axes, tuple) else (axes,)):
                    size *= mesh.shape[a]
            assert dim % size == 0, f"{arch}: {leaf.shape} vs {spec}"


@pytest.mark.parametrize("arch", ["qwen2-72b", "mixtral-8x22b", "olmoe-1b-7b"])
def test_tp_is_used_on_big_weights(arch):
    """The largest 2D weights must actually be sharded on both axes."""
    cfg = get_config(arch)
    model = build_model(cfg)
    abstract = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = shd.param_specs(cfg, _FakeMesh({"data": 16, "model": 16}), abstract)
    flat = jax.tree_util.tree_flatten_with_path(abstract)[0]
    sflat = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    biggest = max(zip(flat, sflat), key=lambda t: np.prod(t[0][1].shape))
    (_, leaf), spec = biggest
    used = [a for a in jax.tree.leaves(tuple(spec)) if a]
    assert "model" in used and "data" in used, (leaf.shape, spec)


def test_moe_ep_vs_tp_rule():
    mesh = _FakeMesh({"data": 16, "model": 16})
    olmoe = shd.make_rules(get_config("olmoe-1b-7b"), mesh)
    mixtral = shd.make_rules(get_config("mixtral-8x22b"), mesh)
    assert olmoe.expert_parallel        # 64 % 16 == 0 -> EP
    assert not mixtral.expert_parallel  # 8 % 16 != 0 -> TP inside experts


def test_cache_specs_long_context():
    """long_500k (batch 1): KV sequence axis must shard over (data, model)."""
    cfg = get_config("zamba2-7b")
    model = build_model(cfg)
    mesh = _FakeMesh({"data": 16, "model": 16})
    abstract = jax.eval_shape(lambda: model.init_cache(1, 524_288))
    specs = shd.cache_specs(cfg, mesh, abstract, batch=1)
    kv_spec = specs["shared_kv"].k if hasattr(specs["shared_kv"], "k") else specs["shared_kv"]["k"]
    assert ("data", "model") in tuple(kv_spec), kv_spec


def test_vocab_not_divisible_falls_back():
    """whisper vocab 51865 + pad 512 -> 52224 divides 16; with padding off
    the spec must drop the TP axis instead of crashing."""
    cfg = get_config("whisper-medium", pad_vocab_multiple=1)
    model = build_model(cfg)
    mesh = _FakeMesh({"data": 16, "model": 16})
    abstract = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = shd.param_specs(cfg, mesh, abstract)
    assert tuple(specs["embed"])[0] is None   # vocab axis replicated


def test_end_to_end_tiny_mesh():
    """jit a sharded train step on a real 1x1 CPU mesh using the same rules
    as production (exercises the full sharding plumbing)."""
    from repro.launch.steps import make_train_step
    from repro.optim.adamw import adamw
    cfg = get_smoke_config("deepseek-7b")
    model = build_model(cfg)
    mesh = make_host_mesh(1, 1)
    params = model.init(jax.random.PRNGKey(0))
    abstract = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = shd.param_specs(cfg, mesh, abstract)
    shardings = shd.named_tree(mesh, specs)
    params = jax.device_put(params, shardings)
    opt = adamw()
    step = make_train_step(model, opt)
    batch = {
        "tokens": jnp.zeros((2, 16), jnp.int32),
        "labels": jnp.zeros((2, 16), jnp.int32),
    }
    with mesh:
        p, o, m = jax.jit(step)(params, opt.init(params), batch)
    assert bool(jnp.isfinite(m["total_loss"]))


def test_input_specs_cover_grid():
    """input_specs must produce abstract inputs for every non-skipped cell."""
    from repro.configs import cell_is_skipped, grid
    cells = grid()
    assert len(cells) == 33          # 40 total - 7 skipped long_500k
    for arch, shape in cells:
        cfg = get_config(arch)
        sds = input_specs(cfg, SHAPES[shape])
        assert all(isinstance(v, jax.ShapeDtypeStruct) for v in sds.values())
