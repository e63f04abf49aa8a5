"""Logical-axis sharding rule engine (DP / FSDP / TP / EP / SP).

Models annotate params and activations with *logical* axis names (see
models/module.py). This module maps logical names → mesh axes with
divisibility-checked fallbacks, so one model definition serves every mesh
(single-pod 16×16, multi-pod 2×16×16, or the 1-device CPU smoke mesh) and
every architecture (e.g. smollm's 9 heads silently fall back to replicated
attention while its d_ff still tensor-parallelizes).

Conventions:
  pod    — pure data parallelism across pods (gradient all-reduce only)
  data   — data parallel + FSDP/ZeRO parameter & optimizer sharding
  model  — tensor parallel (heads / d_ff / vocab) and expert parallel (MoE)
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.module import AxisLeaf, is_axis_leaf

MeshAxes = Union[None, str, Tuple[str, ...]]

# Logical axis → mesh axes. Params use bare names; activations use act_*.
DEFAULT_RULES: Dict[str, MeshAxes] = {
    # parameter axes
    "layers": None,
    "embed": "data",        # FSDP/ZeRO: weights' d_model dim sharded over data
    "mlp": "model",         # TP: FFN hidden
    "heads": "model",       # TP: fused (n_heads·d_head) projection dim
    "kv_heads": "model",    # TP: fused KV projection dim (falls back for MQA)
    "vocab": "model",       # TP: embedding / LM head vocab dim
    "experts": "model",     # EP: MoE expert dim
    "kv_lora": None,        # MLA latent dims stay replicated
    "conv": None,
    "state": None,
    # activation axes
    "act_batch": ("pod", "data"),
    "act_seq": None,         # flipped to "model" under sequence parallelism
    "act_embed": None,
    "act_mlp": "model",
    "act_heads": "model",
    "act_kv_heads": "model",
    "act_vocab": "model",
    "act_experts": "model",
    "act_state": None,
}


class ShardingRules:
    """``units`` counts the indivisible groups along a logical dim (heads
    in a fused ``heads``/``kv_heads`` projection): such a dim shards only
    when its unit count divides too, never splitting a head across shards
    (``ModelConfig.head_units()``)."""

    def __init__(self, mesh: Optional[Mesh],
                 overrides: Optional[Dict[str, MeshAxes]] = None,
                 units: Optional[Dict[str, int]] = None):
        self.mesh = mesh
        self.rules = dict(DEFAULT_RULES)
        if overrides:
            self.rules.update(overrides)
        self.units = dict(units or {})

    def _mesh_size(self, axes: MeshAxes) -> int:
        if axes is None or self.mesh is None:
            return 1
        if isinstance(axes, str):
            axes = (axes,)
        n = 1
        for a in axes:
            n *= dict(zip(self.mesh.axis_names, self.mesh.devices.shape)).get(a, 1)
        return n

    def _resolve(self, axes: MeshAxes) -> MeshAxes:
        """Drop mesh axes that don't exist on the current mesh (e.g. 'pod'
        on the single-pod mesh)."""
        if axes is None or self.mesh is None:
            return None
        names = set(self.mesh.axis_names)
        if isinstance(axes, str):
            return axes if axes in names else None
        kept = tuple(a for a in axes if a in names)
        if not kept:
            return None
        return kept[0] if len(kept) == 1 else kept

    def spec(self, logical: Sequence[Optional[str]],
             shape: Optional[Sequence[int]] = None) -> P:
        """PartitionSpec for the given logical axes, with divisibility
        fallback when ``shape`` is provided.

        A mesh axis may appear at most once in a spec. When two dims
        resolve to the same mesh axis (e.g. sequence parallelism's
        act_seq→model colliding with a TP feature dim), the tensor/feature
        dim wins and the sequence dim replicates — Megatron-SP semantics:
        SP shards the residual stream, TP owns the block interiors.
        """
        parts = []
        for i, name in enumerate(logical):
            axes = self._resolve(self.rules.get(name)) if name else None
            if axes is not None:
                n = max(self._mesh_size(axes), 1)
                if self.units.get(name, n) % n or (
                        shape is not None and shape[i] % n):
                    axes = None  # fallback: replicate this dim
            parts.append(axes)
        # duplicate-axis resolution: act_seq yields first, then earlier dims
        def axes_set(a):
            return set((a,) if isinstance(a, str) else (a or ()))
        seq_dims = [i for i, n in enumerate(logical) if n == "act_seq"]
        order = seq_dims + [i for i in range(len(parts)) if i not in
                            seq_dims]
        used: set = set()
        for i in reversed(order):      # last in order = highest precedence
            a = axes_set(parts[i])
            if a & used:
                parts[i] = None
            else:
                used |= a
        return P(*parts)


_state = threading.local()


def current_rules() -> Optional[ShardingRules]:
    return getattr(_state, "rules", None)


@contextlib.contextmanager
def use_rules(rules: Optional[ShardingRules]):
    prev = getattr(_state, "rules", None)
    _state.rules = rules
    try:
        yield rules
    finally:
        _state.rules = prev


def shard(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """Activation sharding constraint; no-op outside a rules context."""
    rules = current_rules()
    if rules is None or rules.mesh is None:
        return x
    assert len(logical) == x.ndim, (logical, x.shape)
    spec = rules.spec(logical, x.shape)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rules.mesh, spec))


def param_specs(axes_tree, shapes_tree, rules: ShardingRules):
    """Map the (axes, shapes) trees to a PartitionSpec tree."""
    def one(axes_leaf, shape):
        assert is_axis_leaf(axes_leaf), axes_leaf
        shp = shape.shape if hasattr(shape, "shape") else shape
        return rules.spec(tuple(axes_leaf), shp)
    return jax.tree_util.tree_map(one, axes_tree, shapes_tree,
                                  is_leaf=lambda x: is_axis_leaf(x))


def param_shardings(axes_tree, shapes_tree, rules: ShardingRules):
    specs = param_specs(axes_tree, shapes_tree, rules)
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(rules.mesh, s), specs)


def stack_axes(axes_tree):
    """Prepend the 'layers' scan axis to every axes leaf (stacked params)."""
    return jax.tree_util.tree_map(
        lambda a: AxisLeaf(("layers",) + tuple(a)), axes_tree,
        is_leaf=is_axis_leaf)
