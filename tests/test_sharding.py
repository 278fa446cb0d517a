"""Sharding spec rules: correct PartitionSpecs per param family, and the
divisibility validator that makes explicit shardings safe."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, get_reduced_config
from repro.launch.mesh import make_host_mesh
from repro.models import model as M
from repro.sharding.specs import (batch_pspecs, cache_pspecs, fl_pspecs,
                                  param_pspecs, validate_pspecs)


def _find(tree, substr):
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        if substr in key:
            out[key] = leaf
    return out


def test_attention_params_tp_sharded():
    cfg = get_config("phi3-mini-3.8b")
    specs = M.param_specs(cfg)
    ps = param_pspecs(specs)
    wq = list(_find(ps, "attn/wq").values())
    assert wq and all(s[-1] == "model" and s[-2] == "data" for s in wq)
    wo = list(_find(ps, "attn/wo").values())
    assert wo and all(s[-2] == "model" and s[-1] == "data" for s in wo)


def test_moe_experts_expert_parallel():
    cfg = get_config("dbrx-132b")
    ps = param_pspecs(M.param_specs(cfg))
    for key, spec in _find(ps, "experts/w_gate").items():
        # (n_blocks, E, d, ff): experts over model, d over data
        assert spec[-3] == "model" and spec[-2] == "data", (key, spec)


def test_embed_and_head():
    cfg = get_config("yi-34b")
    ps = param_pspecs(M.param_specs(cfg))
    assert ps["embed"] == P("model", None)
    assert ps["lm_head"] == P(None, "model")


def test_norms_replicated():
    cfg = get_config("qwen3-14b")
    ps = param_pspecs(M.param_specs(cfg))
    for key, spec in _find(ps, "norm1").items():
        assert spec == P(), (key, spec)


def test_fsdp_off_drops_data_axis():
    cfg = get_config("phi3-mini-3.8b")
    ps = param_pspecs(M.param_specs(cfg), fsdp=False)
    for key, spec in _find(ps, "attn/wq").items():
        assert "data" not in [s for s in spec if isinstance(s, str)], \
            (key, spec)
        assert spec[-1] == "model"


def test_validate_drops_nondivisible():
    mesh = jax.make_mesh((1,), ("model",))
    # 1-device mesh: axis size 1 divides everything -> keep
    shapes = {"a": jax.ShapeDtypeStruct((7, 8), jnp.float32)}
    out = validate_pspecs(shapes, {"a": P("model", None)}, mesh)
    assert out["a"] == P("model", None)


def test_validate_drops_nondivisible_sim():
    """Simulate a 16-way axis via a fake mesh-shape mapping."""
    class FakeMesh:
        shape = {"model": 16, "data": 16}

    shapes = {"a": jax.ShapeDtypeStruct((51865, 64), jnp.float32),   # vocab!
              "b": jax.ShapeDtypeStruct((64, 128), jnp.float32)}
    out = validate_pspecs(shapes, {"a": P("model", None),
                                   "b": P("data", "model")}, FakeMesh())
    assert out["a"] == P(None, None)          # 51865 % 16 != 0 -> dropped
    assert out["b"] == P("data", "model")     # 64 % 16 == 0, 128 % 16 == 0


def test_batch_pspecs():
    batch = {"tokens": jax.ShapeDtypeStruct((32, 128), jnp.int32),
             "targets": jax.ShapeDtypeStruct((32, 128), jnp.int32)}
    ps = batch_pspecs(batch, batch_axes=("pod", "data"))
    assert ps["tokens"] == P(("pod", "data"), None)


def test_cache_pspecs_seq_shard_when_batch_one():
    """long_500k: b=1 cache shards its sequence dim over data instead of
    replicating the 500k-token KV."""
    cfg = get_reduced_config("phi3-mini-3.8b")
    cache = M.cache_specs(cfg, batch=1, max_len=4096)
    ps = cache_pspecs(cache, batch_axes="data", mesh_batch=16)
    for key, spec in _find(ps, "/k").items():
        assert spec == P(None, None, "data", "model", None), (key, spec)
    # batch divisible -> batch sharding, seq unsharded
    cache2 = M.cache_specs(cfg, batch=32, max_len=4096)
    ps2 = cache_pspecs(cache2, batch_axes="data", mesh_batch=16)
    for key, spec in _find(ps2, "/k").items():
        assert spec == P(None, "data", None, "model", None), (key, spec)


def test_fl_pspecs_stacked_layout():
    stacked = {"w": jnp.zeros((4, 10, 7, 3)), "b": jnp.zeros((4,))}
    ps = fl_pspecs(stacked)
    assert ps["w"] == P("pod", "data", None, None)
    assert ps["b"] == P("pod")


def test_jit_with_specs_on_cpu_mesh():
    """End-to-end: shard a reduced model on the 1-device mesh and run a
    forward under pjit with explicit shardings (exercises to_named)."""
    from repro.sharding.specs import to_named

    cfg = get_reduced_config("phi3-mini-3.8b")
    mesh = make_host_mesh(1, 1)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    p_specs = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    shard = to_named(param_pspecs(p_specs), mesh, p_specs)
    batch = {"tokens": jnp.zeros((2, 8), jnp.int32),
             "targets": jnp.zeros((2, 8), jnp.int32)}

    with jax.set_mesh(mesh):
        f = jax.jit(lambda p, b: M.loss_fn(p, cfg, b),
                    in_shardings=(shard, None))
        lv = f(params, batch)
    assert np.isfinite(float(lv))
