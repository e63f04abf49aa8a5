"""Tensor-parallel execution: shard_map'd GEMM + attention over a mesh.

The GSPMD rule engine (distributed/sharding.py) covers the training and
dry-run paths, where XLA may partition every op automatically. Serving
cannot rely on that alone: the fused/paged attention backends are *Pallas
kernels*, which GSPMD does not partition — they must run per-shard on
shard-local operands. This module is that layer: it wraps
:func:`repro.core.api.matmul`/:func:`~repro.core.api.linear` and
:func:`repro.core.api.attention` in ``shard_map`` over a ``(data, model)``
mesh, so the kernels underneath run unmodified on their local slice.

Layout (Megatron TP, the paper's multi-unit dataflow applied to serving):

  * **column-parallel** projections (QKV, MLP up/gate, LM head): the weight
    is split along N over ``model``; every shard computes its output
    columns from the full K — bitwise identical to the unsharded GEMM.
  * **row-parallel** projections (attention out, MLP down): the weight is
    split along K, each shard contracts its slice, and a ``psum`` over
    ``model`` completes the contraction. Partial products are accumulated
    and summed in fp32 *before* the cast to the model dtype, so the only
    difference from the unsharded GEMM is fp32 summation order.
  * **attention**: heads shard over ``model``; each shard runs the active
    attention backend (fused flash kernel, unfused baseline, or the
    block-table paged kernel) on its head slice. With a paged cache every
    model shard owns its own slice of the page pool — pool tensors
    ``(P, page_size, Hkv, D)`` shard on the KV-head dim, the block table
    replicates, and ``kernels/paged_attention.py`` runs unmodified inside
    the shard_map body (the engine's page accounting is in logical tokens,
    identical on every shard — docs/serving.md).

Head divisibility (``head_sharding``) follows the ShardingRules discipline
— shard only what divides, fall back to replicated otherwise — with one
extra constraint the rules cannot see: backends derive the GQA head→KV-head
grouping from *local* shapes, so query heads may shard without KV heads
only for MQA (Hkv == 1, every query head maps to KV head 0 on any shard).
A GQA slice over replicated KV heads would re-derive a wrong grouping;
those configs replicate attention entirely.

Everything degrades to the plain api.* call when no TP context is active
(or the model axis has size 1), so model code routes through this module
unconditionally and single-device behavior is untouched.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import api
from repro.core.plan import (PackedWeight, QuantizedPackedWeight,
                             ShardingPolicy)
from repro.distributed.sharding import ShardingRules
from repro.models.config import ModelConfig
from repro.models.module import is_axis_leaf

__all__ = [
    "TPContext", "make_context", "use_tp", "current_tp", "head_sharding",
    "linear", "matmul", "attention", "param_shardings", "shard_params",
    "shard_caches",
    "replicate",
]


@dataclasses.dataclass
class TPContext:
    """A mesh + resolved sharding rules, carried thread-local (use_tp)."""

    mesh: Mesh
    rules: ShardingRules
    policy: ShardingPolicy

    @property
    def model_axis(self) -> str:
        return self.policy.model_axis

    @property
    def data_axis(self) -> str:
        return self.policy.data_axis

    @property
    def model_size(self) -> int:
        return int(self.mesh.shape.get(self.model_axis, 1))

    def wants_model(self, logical: Optional[str]) -> bool:
        """True when the rule for ``logical`` resolves to the model axis."""
        if logical is None:
            return False
        axes = self.rules._resolve(self.rules.rules.get(logical))
        if axes is None:
            return False
        axes = (axes,) if isinstance(axes, str) else axes
        return self.model_axis in axes


def make_context(mesh: Optional[Mesh],
                 policy: Optional[ShardingPolicy] = None,
                 cfg: Optional[ModelConfig] = None
                 ) -> Optional[TPContext]:
    """Build a TPContext (None mesh → None, the single-device no-op).

    A model ``cfg`` layers its ``sharding_overrides`` on top of the
    policy's own (e.g. smollm pins heads replicated) and gives its head
    counts, so no head is split across shards.
    """
    if mesh is None:
        return None
    policy = policy if policy is not None else ShardingPolicy()
    merged = policy.overrides_dict()
    units = None
    if cfg is not None:
        merged.update(cfg.overrides_dict())
        units = cfg.head_units()
    return TPContext(mesh=mesh, rules=ShardingRules(mesh, merged, units),
                     policy=policy)


_state = threading.local()


def current_tp() -> Optional[TPContext]:
    return getattr(_state, "tp", None)


@contextlib.contextmanager
def use_tp(ctx: Optional[TPContext]):
    """Pin the active TP context for the enclosed region (thread-local,
    mirrors api.use_policy; read at trace time inside jitted functions)."""
    prev = getattr(_state, "tp", None)
    _state.tp = ctx
    try:
        yield ctx
    finally:
        _state.tp = prev


def replicate(x, ctx: Optional[TPContext] = None):
    """device_put ``x`` replicated over the mesh (host inputs must not be
    left committed to a single device once params/caches span the mesh)."""
    ctx = ctx if ctx is not None else current_tp()
    if ctx is None:
        return jnp.asarray(x)
    return jax.device_put(jnp.asarray(x), NamedSharding(ctx.mesh, P()))


# ---------------------------------------------------------------------------
# Head sharding decision (shared by attention, cache placement, benchmarks)
# ---------------------------------------------------------------------------

def head_sharding(ctx: Optional[TPContext], H: int, Hkv: int
                  ) -> Tuple[bool, bool]:
    """(shard_q, shard_kv) over the model axis for an (H, Hkv) layer.

    Both shard when both divide the model-axis size (rep = H/Hkv is then
    preserved per shard). Query heads shard alone only for MQA (Hkv == 1):
    backends compute the GQA grouping from local shapes, so a GQA query
    slice over replicated KV heads would regroup wrongly — replicate
    instead (see module docstring).
    """
    if ctx is None:
        return False, False
    mp = ctx.model_size
    if mp <= 1 or not ctx.wants_model("heads") or H % mp:
        return False, False
    if H == Hkv:
        return True, True         # MHA/MLA: one head set, one rule
    if ctx.wants_model("kv_heads") and Hkv % mp == 0:
        return True, True
    if Hkv == 1:
        return True, False        # MQA replication fallback
    return False, False


# ---------------------------------------------------------------------------
# shard_map'd GEMM
# ---------------------------------------------------------------------------

def _sharded_dim(ctx: TPContext, name: Optional[str], size: int,
                 units: Optional[int]) -> bool:
    """Does dim ``name`` of width ``size`` shard over the model axis?
    ``units`` is the count of indivisible groups along the dim (head
    boundaries); both it and the raw width must divide."""
    if not ctx.wants_model(name):
        return False
    mp = ctx.model_size
    return size % mp == 0 and (units is None or units % mp == 0)


def linear(x: jax.Array, w, bias=None, *,
           axes: Sequence[Optional[str]],
           units: Optional[int] = None,
           policy=None) -> jax.Array:
    """y = x @ w (+ bias) sharded over the active TP context.

    ``axes`` are the weight's logical axis names (the same pair its init
    recorded — ("embed", "heads") etc.); the rule engine decides which dim,
    if any, carries the model axis. N sharded → column-parallel (bias
    sharded along, output model-sharded on the last dim); K sharded →
    row-parallel (fp32 psum over the contraction, bias applied once after).
    ``units`` bounds the split to whole head groups. Falls back to
    :func:`api.linear` with no context, a trivial model axis, a packed
    weight, or no rule match.
    """
    ctx = current_tp()
    if (ctx is None or ctx.model_size <= 1
            or isinstance(w, (PackedWeight, QuantizedPackedWeight))
            or getattr(w, "ndim", 0) != 2):
        return api.linear(x, w, bias, policy=policy)
    m = ctx.model_axis
    k_name, n_name = axes
    K, N = w.shape

    if _sharded_dim(ctx, n_name, N, units):
        # column parallel: full-K contraction per shard, bitwise identical
        def body(x_, w_, *b_):
            return api.linear(x_, w_, b_[0] if b_ else None, policy=policy)

        xs = P(*([None] * x.ndim))
        in_specs = [xs, P(None, m)]
        operands = [x, w]
        if bias is not None:
            in_specs.append(P(m))
            operands.append(bias)
        fn = shard_map(body, mesh=ctx.mesh, in_specs=tuple(in_specs),
                       out_specs=P(*([None] * (x.ndim - 1)), m),
                       check_vma=False)
        return fn(*operands)

    if _sharded_dim(ctx, k_name, K, units):
        # row parallel: per-shard partial products, fp32 psum, then cast —
        # the sum over model shards happens before the model-dtype rounding
        out_dtype = jnp.promote_types(x.dtype, w.dtype)
        acc = (jnp.float32 if jnp.issubdtype(out_dtype, jnp.floating)
               else None)

        def body(x_, w_):
            part = api.matmul(x_, w_, policy=policy, out_dtype=acc)
            return jax.lax.psum(part, m)

        fn = shard_map(
            body, mesh=ctx.mesh,
            in_specs=(P(*([None] * (x.ndim - 1)), m), P(m, None)),
            out_specs=P(*([None] * x.ndim)), check_vma=False)
        y = fn(x, w)
        if acc is not None:
            y = y.astype(out_dtype)
        if bias is not None:
            y = y + bias
        return y

    return api.linear(x, w, bias, policy=policy)


def matmul(a: jax.Array, b: jax.Array, *,
           axes: Sequence[Optional[str]],
           units: Optional[int] = None,
           policy=None) -> jax.Array:
    """Bias-less :func:`linear` (parity/benchmark cells)."""
    return linear(a, b, None, axes=axes, units=units, policy=policy)


# ---------------------------------------------------------------------------
# shard_map'd attention (heads over model; per-shard paged pools)
# ---------------------------------------------------------------------------

def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              q_positions: jax.Array, kv_valid_len: jax.Array,
              causal: bool = True, scale: Optional[float] = None,
              soft_cap: Optional[float] = None,
              block_tables: Optional[jax.Array] = None,
              kv_scales=None,
              policy=None) -> jax.Array:
    """api.attention with heads sharded over the model axis.

    q is model layout (B, Sq, H, D); k/v are either dense caches
    (B, T, Hkv, D) or, with ``block_tables``, page pools
    (P, page_size, Hkv, D). Either way the head dim is axis 2, so one
    spec covers both: q (and the output) shard on H, k/v shard on Hkv
    when :func:`head_sharding` allows, and positions/lengths/tables
    replicate. An int8 pool's ``kv_scales`` (two (P, Hkv) fp32 arrays)
    shard on their KV-head dim — the LAST — alongside the pools. The
    backend — including the Pallas paged kernel — runs unmodified on its
    shard-local slice.
    """
    ctx = current_tp()
    shard_q, shard_kv = head_sharding(
        ctx, q.shape[2], k.shape[2]) if ctx is not None else (False, False)
    if not shard_q:
        return api.attention(q, k, v, q_positions=q_positions,
                             kv_valid_len=kv_valid_len, causal=causal,
                             scale=scale, soft_cap=soft_cap,
                             block_tables=block_tables,
                             kv_scales=kv_scales, policy=policy)
    pol = policy if policy is not None else api.current_attention_policy()
    if scale is None:
        scale = q.shape[-1] ** -0.5
    m = ctx.model_axis
    hs = P(None, None, m, None)
    kv_spec = hs if shard_kv else P(None, None, None, None)
    operands = [q, k, v, q_positions, kv_valid_len]
    in_specs = [hs, kv_spec, kv_spec, P(None, None), P(None)]
    has_bt = block_tables is not None
    if has_bt:
        operands.append(block_tables)
        in_specs.append(P(None, None))
    has_scales = kv_scales is not None
    if has_scales:
        scale_spec = P(None, m) if shard_kv else P(None, None)
        operands.extend(kv_scales)
        in_specs.extend([scale_spec, scale_spec])

    def body(q_, k_, v_, qp_, kl_, *rest):
        rest = list(rest)
        bt_ = rest.pop(0) if has_bt else None
        sc_ = tuple(rest) if has_scales else None
        return api.attention(q_, k_, v_, q_positions=qp_, kv_valid_len=kl_,
                             causal=causal, scale=scale, soft_cap=soft_cap,
                             block_tables=bt_, kv_scales=sc_, policy=pol)

    fn = shard_map(body, mesh=ctx.mesh, in_specs=tuple(in_specs),
                   out_specs=hs, check_vma=False)
    return fn(*operands)


# ---------------------------------------------------------------------------
# Placement: params + caches resident in their shard_map layout
# ---------------------------------------------------------------------------

def _model_only(spec: P, ctx: TPContext) -> P:
    """Strip every mesh axis except the model axis from a PartitionSpec —
    TP serving replicates weights along data/pod (no FSDP at inference)."""
    m = ctx.model_axis

    def keep(entry):
        axes = (entry,) if isinstance(entry, str) else (entry or ())
        return m if m in axes else None

    return P(*(keep(e) for e in spec))


def param_shardings(axes_tree, params, ctx: TPContext):
    """The NamedSharding of every param in the layout tp.linear's in_specs
    expect: model-axis dims sharded, everything else replicated. ``params``
    may hold arrays or shapes (``jax.eval_shape``)."""
    def one(axes_leaf, param):
        spec = (_model_only(ctx.rules.spec(tuple(axes_leaf), param.shape),
                            ctx)
                if is_axis_leaf(axes_leaf) else P())
        return NamedSharding(ctx.mesh, spec)

    return jax.tree_util.tree_map(one, axes_tree, params,
                                  is_leaf=is_axis_leaf)


def shard_params(params, axes_tree, ctx: Optional[TPContext]):
    """device_put every param in its :func:`param_shardings` layout.
    Placement is a performance property only — shard_map slices the global
    value per its specs regardless — but resident placement avoids
    re-distributing every weight on every step."""
    if ctx is None:
        return params
    return jax.device_put(params, param_shardings(axes_tree, params, ctx))


_KV_LEAVES = ("k", "v", "kp", "vp")
_KV_SCALE_LEAVES = ("k_scale", "v_scale")


def shard_caches(caches, ctx: Optional[TPContext], *, shard_kv: bool):
    """device_put decode caches: K/V leaves (dense ``k``/``v`` slabs or
    paged ``kp``/``vp`` pools, stacked or not) shard on their KV-head dim
    (always axis -2) when ``shard_kv``; int8 pools' ``k_scale``/``v_scale``
    side-tensors shard on *their* KV-head dim (the last — (…, P, Hkv));
    lengths, block tables, MLA latent and SSM state replicate. ``shard_kv``
    must be the :func:`head_sharding` decision for the model's (H, Hkv),
    so placement agrees with tp.attention's in_specs."""
    if ctx is None:
        return caches
    mesh, m = ctx.mesh, ctx.model_axis

    def rec(node):
        if isinstance(node, dict):
            out = {}
            for key, val in node.items():
                if isinstance(val, (dict, list, tuple)):
                    out[key] = rec(val)
                elif (key in _KV_LEAVES and shard_kv
                      and getattr(val, "ndim", 0) >= 4
                      and val.shape[-2] % ctx.model_size == 0):
                    spec = P(*([None] * (val.ndim - 2)), m, None)
                    out[key] = jax.device_put(val, NamedSharding(mesh, spec))
                elif (key in _KV_SCALE_LEAVES and shard_kv
                      and getattr(val, "ndim", 0) >= 2
                      and val.shape[-1] % ctx.model_size == 0):
                    spec = P(*([None] * (val.ndim - 1)), m)
                    out[key] = jax.device_put(val, NamedSharding(mesh, spec))
                else:
                    out[key] = jax.device_put(val,
                                              NamedSharding(mesh, P()))
            return out
        if isinstance(node, (list, tuple)):
            return type(node)(rec(v) for v in node)
        return jax.device_put(node, NamedSharding(mesh, P()))

    return rec(caches)
