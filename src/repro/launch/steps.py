"""pjit-able step functions + sharding trees for the dry-run and launchers."""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.registry import ShapeCell
from repro.distributed import sharding as shd
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.optim import AdamWConfig, adamw_update, cosine_schedule


def make_train_step_fn(cfg: ModelConfig, opt_cfg: Optional[AdamWConfig] = None,
                       total_steps: int = 10000, microbatches: int = 1,
                       grad_shardings=None):
    """One optimizer step. With microbatches > 1, the global batch is split
    and gradients are accumulated in a lax.scan — same math, same total
    FLOPs/bytes, but the live activation working set divides by the count
    (the standard fit-on-chip lever for the train_4k cells).

    grad_shardings (optional, = the param sharding tree): constrains each
    gradient to its parameter's sharding at the autodiff boundary, steering
    GSPMD to reduce-scatter (half the bytes, sharded result) instead of
    all-reduce + slice for the data-parallel gradient reduction — §Perf H4."""
    opt_cfg = opt_cfg or AdamWConfig()

    def grad_of(params, batch):
        def loss_fn(p):
            return T.lm_loss(p, cfg, batch)
        (loss, metrics), grads = jax.value_and_grad(loss_fn,
                                                    has_aux=True)(params)
        if grad_shardings is not None:
            grads = jax.tree_util.tree_map(
                jax.lax.with_sharding_constraint, grads, grad_shardings)
        return (loss, metrics), grads

    def train_step(params, opt_state, batch):
        if microbatches == 1:
            (loss, metrics), grads = grad_of(params, batch)  # noqa: E501
        else:
            def split(x):
                return x.reshape((microbatches, x.shape[0] // microbatches)
                                 + x.shape[1:])
            mb = jax.tree_util.tree_map(split, batch)

            def acc_body(carry, mbatch):
                g_acc, l_acc = carry
                (loss_mb, _), g = grad_of(params, mbatch)
                g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
                return (g_acc, l_acc + loss_mb), None

            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss), _ = jax.lax.scan(acc_body,
                                            (g0, jnp.zeros((), jnp.float32)),
                                            mb)
            scale = 1.0 / microbatches
            grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
            loss = loss * scale
            metrics = {"ce": loss, "aux": jnp.zeros((), jnp.float32)}
        lr = cosine_schedule(opt_state["step"], base_lr=opt_cfg.lr,
                             warmup=100, total=total_steps)
        params, opt_state, om, _ = adamw_update(params, grads, opt_state,
                                                opt_cfg, lr)
        return params, opt_state, dict(metrics, loss=loss, **om)

    return train_step


# ---------------------------------------------------------------------------
# Sharding trees
# ---------------------------------------------------------------------------

def batch_shardings(batch_specs, rules: shd.ShardingRules):
    """tokens/labels (B,S[,CB]) and embeds (B,N,D) shard batch over DP."""
    def one(leaf):
        dims = ["act_batch"] + [None] * (len(leaf.shape) - 1)
        return NamedSharding(rules.mesh, rules.spec(dims, leaf.shape))
    return jax.tree_util.tree_map(one, batch_specs)


_CACHE_DIM_RULES = {
    "k": ("act_batch", "act_kv_seq", "act_kv_heads", None),
    "v": ("act_batch", "act_kv_seq", "act_kv_heads", None),
    "ckv": ("act_batch", "act_kv_seq", None),
    "krope": ("act_batch", "act_kv_seq", None),
    "len": ("act_batch",),
    "x": ("act_batch", None, "act_mlp"),      # conv state
    "B": ("act_batch", None, None),
    "C": ("act_batch", None, None),
    "state": ("act_batch", "act_heads", None, None),
}


def cache_shardings(cache_specs, rules: shd.ShardingRules):
    """Right-aligned role-based specs; leading (layer-stack) dims replicate."""
    def one(path, leaf):
        name = None
        for entry in reversed(path):
            key = getattr(entry, "key", None) or getattr(entry, "idx", None)
            if isinstance(key, str):
                name = key
                break
        dims = _CACHE_DIM_RULES.get(name)
        if dims is None:
            spec = P()
        else:
            pad = (None,) * (len(leaf.shape) - len(dims))
            spec = rules.spec(pad + tuple(dims), leaf.shape)
        return NamedSharding(rules.mesh, spec)
    return jax.tree_util.tree_map_with_path(one, cache_specs)


def model_shardings(cfg: ModelConfig, params_abs, axes,
                    rules: shd.ShardingRules):
    return shd.param_shardings(axes, params_abs, rules)


def opt_shardings(param_shardings_tree, rules: shd.ShardingRules):
    return {
        "mu": param_shardings_tree,
        "nu": param_shardings_tree,
        "step": NamedSharding(rules.mesh, P()),
    }


def make_rules(cfg: ModelConfig, mesh, shape: Optional[ShapeCell] = None,
               extra_overrides: Optional[Dict] = None) -> shd.ShardingRules:
    overrides = cfg.overrides_dict()
    if shape is not None and shape.name == "long_500k":
        # SP for the huge decode context: shard cache seq + SSM state heads
        overrides.setdefault("act_kv_seq", "data")
    if extra_overrides:
        overrides.update(extra_overrides)
    return shd.ShardingRules(mesh, overrides, cfg.head_units())
