"""Sharding rule engine: logical→mesh mapping, divisibility fallbacks,
mesh-axis dropping, and param-spec trees."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.registry import get_config
from repro.distributed import sharding as shd
from repro.models.module import ax


def one_device_mesh(axes=("data", "model")):
    dev = np.array(jax.devices()[:1]).reshape((1,) * len(axes))
    return Mesh(dev, axes)


def test_spec_basic_mapping():
    rules = shd.ShardingRules(one_device_mesh())
    assert rules.spec(("embed", "mlp")) == P("data", "model")
    assert rules.spec(("vocab", "embed")) == P("model", "data")
    assert rules.spec((None, "heads")) == P(None, "model")


def test_divisibility_fallback():
    """9 heads on a 16-way model axis must fall back to replicated — but only
    when a shape is provided to check against."""
    mesh = one_device_mesh()
    rules = shd.ShardingRules(mesh)
    # fake a 16-wide model axis by overriding _mesh_size via a fabricated mesh
    class Fake(shd.ShardingRules):
        def _mesh_size(self, axes):
            return 16 if axes == "model" else 1
    rules = Fake(mesh)
    spec = rules.spec(("embed", "heads"), shape=(576, 9 * 64))
    assert spec == P("data", "model")          # 576 % 16 == 0 on dim1
    spec = rules.spec((None, "heads"), shape=(1, 9))
    assert spec == P(None, None)               # 9 % 16 != 0 → replicate


@pytest.mark.parametrize("model,kv_spec", [(4, "model"), (16, None)])
def test_head_units_keep_heads_whole(model, kv_spec):
    """qwen3-8b's fused KV projection is 1024 wide, which 16 divides, but
    its 8 KV heads do not: at TP=16 it replicates rather than split a head;
    at TP=4 it shards, two heads a shard. Query heads (32) shard at both."""
    cfg = get_config("qwen3-8b")

    class Fake(shd.ShardingRules):
        def _mesh_size(self, axes):
            return model if axes == "model" else 1
    rules = Fake(one_device_mesh(), cfg.overrides_dict(), cfg.head_units())
    kv_width = cfg.n_kv_heads * cfg.head_dim
    assert rules.spec(("embed", "kv_heads"), (cfg.d_model, kv_width)) == P(
        "data", kv_spec)
    assert rules.spec(("embed", "heads")) == P("data", "model")


def test_missing_mesh_axis_dropped():
    """'pod' doesn't exist on the single-pod mesh → silently dropped."""
    mesh = one_device_mesh(("data", "model"))
    rules = shd.ShardingRules(mesh)
    assert rules.spec(("act_batch",)) == P("data")   # ("pod","data") → data


def test_overrides_take_precedence():
    rules = shd.ShardingRules(one_device_mesh(), overrides={"heads": None})
    assert rules.spec(("embed", "heads")) == P("data", None)


def test_smollm_overrides_replicate_attention():
    cfg = get_config("smollm-135m")
    rules = shd.ShardingRules(one_device_mesh(), cfg.overrides_dict())
    assert rules.spec(("embed", "heads")) == P("data", None)
    assert rules.spec(("embed", "mlp")) == P("data", "model")  # d_ff still TP


def test_param_specs_tree():
    rules = shd.ShardingRules(one_device_mesh())
    axes = {"w": ax("embed", "mlp"), "b": ax("mlp"),
            "nested": {"v": ax("vocab", "embed")}}
    shapes = {"w": jax.ShapeDtypeStruct((128, 256), jnp.float32),
              "b": jax.ShapeDtypeStruct((256,), jnp.float32),
              "nested": {"v": jax.ShapeDtypeStruct((512, 128), jnp.float32)}}
    specs = shd.param_specs(axes, shapes, rules)
    assert specs["w"] == P("data", "model")
    assert specs["b"] == P("model")
    assert specs["nested"]["v"] == P("model", "data")


def test_shard_noop_without_rules():
    x = jnp.ones((4, 4))
    y = shd.shard(x, "act_batch", None)
    assert y is x


def test_stack_axes_prepends_layers():
    axes = {"w": ax("embed", "mlp")}
    stacked = shd.stack_axes(axes)
    assert tuple(stacked["w"]) == ("layers", "embed", "mlp")


def test_use_rules_context():
    mesh = one_device_mesh()
    rules = shd.ShardingRules(mesh)
    assert shd.current_rules() is None
    with shd.use_rules(rules):
        assert shd.current_rules() is rules
        x = shd.shard(jnp.ones((2, 2)), "act_batch", None)
        assert x.shape == (2, 2)
    assert shd.current_rules() is None


def test_make_host_mesh_model_factor():
    """Regression: make_host_mesh silently pinned the model axis to 1 — a
    caller asking for TP got a mesh that could never shard. It now takes
    the model factor and fails loudly on an impossible split."""
    from repro.launch.mesh import make_host_mesh
    n = len(jax.devices())
    mesh = make_host_mesh()                      # default: all-data, TP=1
    assert dict(mesh.shape) == {"data": n, "model": 1}
    mesh = make_host_mesh(model=n)               # all-model
    assert dict(mesh.shape) == {"data": 1, "model": n}
    with pytest.raises(ValueError, match="model"):
        make_host_mesh(model=0)
    bad = n + 1                                  # never divides n
    with pytest.raises(ValueError, match="divisible"):
        make_host_mesh(model=bad)
