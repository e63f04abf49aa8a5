"""ExecutionPlan API: typed GEMM policies, backend registry, resident weights.

The paper's co-design claim (§3.3, Fig. 5) is that transformer GEMMs stay
fast because operands *stay* block-major across layers: weights are laid out
offline, exactly once, and every activation is already block-major because it
was written as the previous GEMM's C blocks. This module is the API that
expresses that property:

  * :class:`GemmPolicy` — a frozen, hashable description of *how* GEMMs
    should execute (backend, DC/DM access mode, layout override, accumulator
    dtype, VMEM budget). Replaces the old thread-local string switch.
  * the **backend registry** — :func:`register_backend` maps a policy's
    backend name to an implementation, replacing the if/elif chain the old
    ``api.matmul`` carried. Downstream autotuning/sharding backends plug in
    without touching dispatch.
  * :func:`plan` — resolves a :class:`GemmPolicy` against a concrete
    ``(M, N, K, dtype)`` problem into an :class:`ExecutionPlan` holding the
    chosen :class:`~repro.core.layout.BlockLayout`. ``mode="auto"`` consults
    the analytic system model (:mod:`repro.core.sysmodel`) to pick DC vs DM
    per shape. Plans are memoized in a process-wide cache keyed on
    ``(shape, dtype, policy)`` so repeated shapes (every decode step, every
    layer of the same width) resolve exactly once.
  * :class:`PackedWeight` — a weight held *resident in block-major form*
    (the paper's horizontally-split B operand, Fig. 4 bottom). Layers pack
    each weight once at model build; every subsequent GEMM consumes the
    blocks directly — the Fig. 5 pipeline-reuse property.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import layout as L
from repro.core import quant as Q
from repro.core.quant import QuantizedPackedWeight

__all__ = [
    "GemmPolicy", "ExecutionPlan", "PackedWeight", "QuantizedPackedWeight",
    "BackendSpec",
    "plan", "plan_cache_info", "plan_cache_clear",
    "register_backend", "unregister_backend", "get_backend_spec",
    "registered_backends", "resolve_backend",
    "pack_weight", "pack_model_weights", "layout_for_packed",
    "AttentionPolicy", "AttentionBackendSpec",
    "register_attention_backend", "unregister_attention_backend",
    "get_attention_backend_spec", "registered_attention_backends",
    "resolve_attention_backend",
    "ShardingPolicy",
]

DEFAULT_VMEM_BUDGET = 96 * 1024 * 1024

# Weight dtypes the quantized GEMM route understands (core/quant.py).
_WEIGHT_DTYPES = (None, "int8")

# KV-pool dtypes the paged attention route understands (docs/quant.md).
_KV_DTYPES = (None, "int8")


# ---------------------------------------------------------------------------
# Policy
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GemmPolicy:
    """How GEMMs should execute. Frozen → hashable → a plan-cache key.

    backend      registry name, or "auto" (pallas on TPU, xla elsewhere).
    mode         paper access mode: "dc" | "dm" | "auto" (per-shape choice by
                 the sysmodel analytic cost model).
    layout       explicit BlockLayout override (skips mode resolution).
    acc_dtype    accumulator dtype name ("float32"/"int32"); None → the
                 paper's MAC policy (int inputs → int32, float → float32).
    vmem_budget  VMEM bytes the layout chooser may claim for the working set.
    weight_dtype None → weights execute in their stored dtype; "int8" →
                 GEMM weights run the quantized W8A8 route (core/quant.py):
                 per-channel int8 weights, dynamic per-row int8 activations,
                 int32 accumulation, dequant fused into the C-block flush.
    """

    backend: str = "auto"
    mode: str = "auto"
    layout: Optional[L.BlockLayout] = None
    acc_dtype: Optional[str] = None
    vmem_budget: int = DEFAULT_VMEM_BUDGET
    weight_dtype: Optional[str] = None

    def __post_init__(self):
        if self.weight_dtype not in _WEIGHT_DTYPES:
            raise ValueError(
                f"unsupported weight_dtype {self.weight_dtype!r}; "
                f"expected one of {_WEIGHT_DTYPES}")
        if self.weight_dtype is not None and self.acc_dtype is not None:
            raise ValueError(
                f"acc_dtype={self.acc_dtype!r} cannot be combined with "
                f"weight_dtype={self.weight_dtype!r}: the quantized route "
                "accumulates int8×int8 in int32 by construction (the "
                "rank-1 dequant is exact only over the integer result)")

    def resolved_backend(self) -> str:
        return resolve_backend(self.backend)


# Common pinned policies (tests, benchmarks, CLI flags).
XLA = GemmPolicy(backend="xla")
BLOCKFLOW = GemmPolicy(backend="blockflow")
PALLAS = GemmPolicy(backend="pallas")


def on_tpu() -> bool:
    """Whether JAX runs on a TPU: the one test behind the platform defaults
    below and behind running the Pallas kernels compiled, not interpreted."""
    return jax.default_backend() == "tpu"


def resolve_backend(name: str) -> str:
    """Map "auto" to the platform default; pass anything else through."""
    if name != "auto":
        return name
    return "pallas" if on_tpu() else "xla"


# ---------------------------------------------------------------------------
# Backend registry
# ---------------------------------------------------------------------------

# A backend implementation: fn(a, b, plan, out_dtype) -> c.
#   * batched=False backends receive a 2-D a (M, K) and a 2-D b (K, N) or a
#     PackedWeight; api.matmul collapses/vmaps leading dims around them.
#   * batched=True backends receive the operands as the caller passed them
#     (any leading dims, jnp broadcasting semantics) — e.g. XLA einsum.
BackendFn = Callable[..., jax.Array]


@dataclasses.dataclass(frozen=True)
class BackendSpec:
    name: str
    fn: BackendFn
    batched: bool = False        # consumes batched contractions natively
    needs_layout: bool = True    # plan() must resolve a BlockLayout


_REGISTRY: Dict[str, BackendSpec] = {}
_registry_lock = threading.Lock()


def register_backend(name: str, fn: BackendFn, *, batched: bool = False,
                     needs_layout: bool = True,
                     overwrite: bool = False) -> BackendSpec:
    """Register a GEMM backend under ``name`` (the GemmPolicy.backend key)."""
    spec = BackendSpec(name=name, fn=fn, batched=batched,
                       needs_layout=needs_layout)
    with _registry_lock:
        if name in _REGISTRY and not overwrite:
            raise ValueError(f"backend {name!r} already registered "
                             f"(pass overwrite=True to replace)")
        _REGISTRY[name] = spec
    plan_cache_clear()   # plans embed the backend name; don't serve stale ones
    return spec


def unregister_backend(name: str) -> None:
    with _registry_lock:
        _REGISTRY.pop(name, None)
    plan_cache_clear()


def get_backend_spec(name: str) -> BackendSpec:
    spec = _REGISTRY.get(resolve_backend(name))
    if spec is None:
        # The built-ins are registered by repro.core.api at import time;
        # make plan.py usable standalone by pulling them in on first miss.
        import repro.core.api  # noqa: F401  (registers built-in backends)
        spec = _REGISTRY.get(resolve_backend(name))
    if spec is None:
        raise ValueError(
            f"unknown GEMM backend {name!r}; registered: "
            f"{sorted(_REGISTRY)}")
    return spec


def registered_backends() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


# ---------------------------------------------------------------------------
# Attention policy + backend registry (mirrors the GEMM registry above)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttentionPolicy:
    """How attention executes. Frozen → hashable → jit-static.

    backend    registry name, or "auto" (fused Pallas kernel on TPU, the
               unfused einsum + host-softmax baseline elsewhere — mirroring
               the GEMM registry's pallas/xla auto split).
    block_q    flash-kernel query-block rows (fused/paged backends).
    block_k    flash-kernel key-block columns (fused backends only).
    page_size  tokens per KV page for the ``paged`` backends: the key-block
               size of the paged kernel IS the page size, so keep it
               MXU-friendly (a multiple of the sublane tile; the fused
               kernel's block_k is its natural TPU value). Consumed by
               ``models/transformer.py::init_paged_caches`` and the serving
               engine's PagePool (serving/kv_pool.py, docs/serving.md).
    kv_dtype   None → the KV pool stores the model's cache dtype; "int8" →
               paged backends store int8 pages with per-page-per-head fp32
               scales, dequantized inside the kernel's K/V-block fetch
               (docs/quant.md#kv-pages). Paged backends only — the dense
               backends reject it (core/api.py).

    All backends share one contract (kernels/ref.py::mha_ref): key j of
    batch row b is visible to query i iff ``j < kv_valid_len[b]`` and, when
    causal, ``j <= q_positions[b, i]``; rows with no visible key (serving's
    masked position −1 slots) produce zeros. The paged backends add one
    input — a per-request block table mapping logical key blocks to
    physical pool pages — and keep the same logical-position semantics.
    """

    backend: str = "auto"
    block_q: int = 128
    block_k: int = 128
    page_size: int = 16
    kv_dtype: Optional[str] = None

    def __post_init__(self):
        if self.page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {self.page_size}")
        if self.kv_dtype not in _KV_DTYPES:
            raise ValueError(
                f"unsupported kv_dtype {self.kv_dtype!r}; "
                f"expected one of {_KV_DTYPES}")

    def resolved_backend(self) -> str:
        return resolve_attention_backend(self.backend)


# Common pinned policies (tests, benchmarks, CLI flags).
FUSED = AttentionPolicy(backend="fused")
UNFUSED = AttentionPolicy(backend="unfused")
PAGED = AttentionPolicy(backend="paged")


def resolve_attention_backend(name: str) -> str:
    """Map "auto" to the platform default; pass anything else through."""
    if name != "auto":
        return name
    return "fused" if on_tpu() else "unfused"


# ---------------------------------------------------------------------------
# Sharding policy (consumed by repro/distributed/tp.py)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardingPolicy:
    """How execution shards over a (data, model) device mesh.

    The third member of the policy family (GemmPolicy: how GEMMs execute;
    AttentionPolicy: how attention executes; ShardingPolicy: how both span
    a mesh). Frozen → hashable → safe to carry in jit-static config. The
    mesh itself is a runtime handle (ServeConfig.mesh, launch/mesh.py);
    this policy only names the axes and rule overrides.

    data_axis    mesh axis for data parallelism (activations' batch dim;
                 TP serving keeps weights/caches replicated along it).
    model_axis   mesh axis for tensor parallelism: QKV/up projections
                 column-parallel, out/down projections row-parallel with a
                 psum on the contraction, attention/KV-pool heads sharded
                 (repro/distributed/tp.py, docs/serving.md).
    overrides    logical-rule overrides layered onto
                 :data:`repro.distributed.sharding.DEFAULT_RULES`, as a
                 hashable tuple of ``(logical_name, mesh_axes)`` pairs —
                 e.g. ``(("heads", None),)`` pins attention replicated.
    """

    data_axis: str = "data"
    model_axis: str = "model"
    overrides: Tuple[Tuple[str, Any], ...] = ()

    def overrides_dict(self) -> Dict[str, Any]:
        return dict(self.overrides)


# An attention backend implementation:
#   fn(q, k, v, *, q_positions, kv_valid_len, causal, scale, soft_cap,
#      policy, block_tables) -> out
# with model-layout operands: q (B,Sq,H,Dk), k (B,T,Hkv,Dk), v (B,T,Hkv,Dv),
# returning (B,Sq,H,Dv). block_tables is None for dense caches; the paged
# backends instead receive pool-shaped k/v (P, page_size, Hkv, D) plus the
# (B, n_blocks) block table (docs/serving.md); dense backends must reject a
# non-None block table rather than misread the pool layout.
AttentionBackendFn = Callable[..., jax.Array]


@dataclasses.dataclass(frozen=True)
class AttentionBackendSpec:
    name: str
    fn: AttentionBackendFn


_ATTN_REGISTRY: Dict[str, AttentionBackendSpec] = {}


def register_attention_backend(name: str, fn: AttentionBackendFn, *,
                               overwrite: bool = False) -> AttentionBackendSpec:
    """Register an attention backend under ``name`` (the
    AttentionPolicy.backend key)."""
    spec = AttentionBackendSpec(name=name, fn=fn)
    with _registry_lock:
        if name in _ATTN_REGISTRY and not overwrite:
            raise ValueError(f"attention backend {name!r} already registered "
                             f"(pass overwrite=True to replace)")
        _ATTN_REGISTRY[name] = spec
    return spec


def unregister_attention_backend(name: str) -> None:
    with _registry_lock:
        _ATTN_REGISTRY.pop(name, None)


def get_attention_backend_spec(name: str) -> AttentionBackendSpec:
    spec = _ATTN_REGISTRY.get(resolve_attention_backend(name))
    if spec is None:
        # Built-ins are registered by repro.core.api at import time; make
        # plan.py usable standalone by pulling them in on first miss.
        import repro.core.api  # noqa: F401  (registers built-in backends)
        spec = _ATTN_REGISTRY.get(resolve_attention_backend(name))
    if spec is None:
        raise ValueError(
            f"unknown attention backend {name!r}; registered: "
            f"{sorted(_ATTN_REGISTRY)}")
    return spec


def registered_attention_backends() -> Tuple[str, ...]:
    return tuple(sorted(_ATTN_REGISTRY))


# ---------------------------------------------------------------------------
# Plan resolution
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """A GemmPolicy resolved against one (M, N, K, dtype) problem."""

    M: int
    N: int
    K: int
    dtype: str                       # canonical jnp dtype name
    backend: str                     # resolved registry name
    mode: Optional[str]              # "dc"/"dm"; None for layout-free backends
    layout: Optional[L.BlockLayout]
    acc_dtype: str
    policy: GemmPolicy

    @property
    def acc(self) -> jnp.dtype:
        return jnp.dtype(self.acc_dtype)


_SYSMODEL_DTYPE = {"int8": "int8", "int16": "int16", "int32": "int32",
                   "float16": "fp16", "bfloat16": "bf16", "float32": "fp32"}


def _default_acc_dtype(dtype: jnp.dtype) -> str:
    from repro.core import blockflow  # single source for the MAC acc policy
    return blockflow.acc_dtype_for(dtype).name


def _auto_mode(M: int, N: int, K: int, dtype: str) -> str:
    """DC vs DM per shape, from the analytic system model (paper §4.3).

    DC's LLC streaming wins while the C strip stays cache-resident; DM's
    large bursts win once it does not. The sysmodel encodes exactly that
    cliff, so we ask it instead of hardcoding a default.
    """
    from repro.core import sysmodel as SM  # deferred: keep import cost off
    g = SM.Gemm(M=M, K=K, N=N)
    sm_dtype = _SYSMODEL_DTYPE.get(dtype, "fp32")
    t_dc = SM.matrixflow_gemm_time(g, sm_dtype, mode="dc")["total"]
    t_dm = SM.matrixflow_gemm_time(g, sm_dtype, mode="dm")["total"]
    return "dc" if t_dc <= t_dm else "dm"


@functools.lru_cache(maxsize=4096)
def _plan_cached(M: int, N: int, K: int, dtype: str,
                 policy: GemmPolicy) -> ExecutionPlan:
    backend = policy.resolved_backend()
    spec = get_backend_spec(backend)
    acc = policy.acc_dtype or _default_acc_dtype(dtype)
    if not spec.needs_layout:
        return ExecutionPlan(M=M, N=N, K=K, dtype=dtype, backend=backend,
                             mode=None, layout=None, acc_dtype=acc,
                             policy=policy)
    if policy.layout is not None:
        layout = policy.layout
        mode = layout.mode
    else:
        mode = policy.mode
        if mode == "auto":
            mode = _auto_mode(M, N, K, dtype)
        layout = L.choose_layout(M, N, K, jnp.dtype(dtype), mode=mode,
                                 vmem_budget=policy.vmem_budget)
    return ExecutionPlan(M=M, N=N, K=K, dtype=dtype, backend=backend,
                         mode=mode, layout=layout, acc_dtype=acc,
                         policy=policy)


def plan(M: int, N: int, K: int, dtype: Any,
         policy: Optional[GemmPolicy] = None, *,
         validate: bool = False) -> ExecutionPlan:
    """Resolve ``policy`` for one GEMM problem; memoized on all arguments.

    ``validate=True`` additionally runs the resolved block choice through
    the static contract checker (repro/analysis): the auto-mode layout is
    instantiated as the kernel's registered :class:`KernelContract` and
    checked for coverage/bounds/race violations before anything executes.
    Raises :class:`~repro.analysis.kernel_contracts.ContractViolationError`
    on the first bad plan — the gate ``python -m repro.analysis`` sweeps.
    """
    p = _plan_cached(int(M), int(N), int(K), jnp.dtype(dtype).name,
                     policy if policy is not None else GemmPolicy())
    if validate:
        _validate_plan(p)
    return p


def _validate_plan(p: ExecutionPlan) -> None:
    """Check a resolved plan's block geometry against the kernel contract
    it will dispatch to (lazy import: analysis is optional at runtime)."""
    if p.layout is None:
        return                      # layout-free backend (xla): no contract
    from repro.analysis.kernel_contracts import (ContractViolationError,
                                                 check_contract,
                                                 get_contract_builder)
    blk = p.layout
    nbm = -(-p.M // blk.bm)
    nbn = -(-p.N // blk.bn)
    nbk = -(-p.K // blk.bk)
    if p.backend == "blockflow":
        contract = get_contract_builder("blockflow")(
            nbm=nbm, nbn=nbn, nbk=nbk)
    else:
        contract = get_contract_builder("matrixflow_gemm")(
            a_shape=(nbm, nbk, blk.bm, blk.bk),
            b_shape=(nbn, nbk, blk.bk, blk.bn),
            blk=blk, fused=p.policy.weight_dtype == "int8")
    violations = check_contract(contract)
    if violations:
        raise ContractViolationError(violations)


def plan_cache_info():
    """Hits/misses of the process-wide plan cache (functools CacheInfo)."""
    return _plan_cached.cache_info()


def plan_cache_clear() -> None:
    _plan_cached.cache_clear()


# ---------------------------------------------------------------------------
# Resident block-major weights (paper Fig. 5: lay out once, reuse per layer)
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class PackedWeight:
    """A GEMM rhs stored block-major (the paper's horizontally-split B).

    data is ``(..., N/bn, K/bk, bk, bn)``; leading dims are stacked-layer
    axes (lax.scan / tree indexing slice only ``data``, so a stacked
    PackedWeight indexes down to a per-layer one for free).
    """

    data: jax.Array
    k: int                   # logical (unpadded) K
    n: int                   # logical (unpadded) N
    bk: int
    bn: int
    mode: str = "dm"

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.k, self.n)

    @property
    def dtype(self):
        return self.data.dtype

    def unpack(self) -> jax.Array:
        """Back to row-major (…, K, N) — for layout-free backends."""
        return L.from_block_major_b(self.data, self.k, self.n)

    # pytree protocol: data is the only traced leaf; geometry is static.
    def tree_flatten(self):
        return (self.data,), (self.k, self.n, self.bk, self.bn, self.mode)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)


def pack_weight(w: jax.Array, policy: Optional[GemmPolicy] = None,
                *, m_hint: int = 512, quantize: Optional[str] = None):
    """Lay a (…, K, N) weight out block-major exactly once.

    ``m_hint`` stands in for the unknown runtime M when resolving the block
    geometry; bk/bn depend on M only through the VMEM-budget shrink loop, so
    any M that fits the budget yields the same packing.

    ``quantize="int8"`` (default: the policy's ``weight_dtype``) quantizes
    symmetric per-channel at pack time and returns a
    :class:`QuantizedPackedWeight` — int8 blocks + fp32 scales, resident —
    instead of a fp :class:`PackedWeight`. Block geometry is then chosen for
    the int8 itemsize (the paper's per-dtype MAC sizing, Table 2).
    """
    policy = policy if policy is not None else GemmPolicy()
    quantize = quantize if quantize is not None else policy.weight_dtype
    if quantize not in _WEIGHT_DTYPES:
        raise ValueError(f"unsupported quantize={quantize!r}; "
                         f"expected one of {_WEIGHT_DTYPES}")
    K, N = w.shape[-2], w.shape[-1]
    pack_dtype = jnp.dtype(jnp.int8) if quantize == "int8" else w.dtype
    if policy.layout is not None:
        blk = policy.layout
    else:
        mode = policy.mode
        if mode == "auto":
            mode = _auto_mode(m_hint, N, K, jnp.dtype(pack_dtype).name)
        blk = L.choose_layout(m_hint, N, K, pack_dtype, mode=mode,
                              vmem_budget=policy.vmem_budget)
    if quantize == "int8":
        q, scales = Q.quantize_weight(w)
        data = L.to_block_major_b(q, blk.bk, blk.bn)
        return QuantizedPackedWeight(
            data=data, scales=scales, k=K, n=N, bk=blk.bk, bn=blk.bn,
            mode=blk.mode, dequant_dtype=jnp.dtype(w.dtype).name)
    data = L.to_block_major_b(w, blk.bk, blk.bn)
    return PackedWeight(data=data, k=K, n=N, bk=blk.bk, bn=blk.bn,
                        mode=blk.mode)


def layout_for_packed(M: int, pw, dtype: Any,
                      policy: Optional[GemmPolicy] = None) -> L.BlockLayout:
    """A BlockLayout consistent with a packed weight's frozen bk/bn.

    ``pw`` is a :class:`PackedWeight` or :class:`QuantizedPackedWeight`
    (both carry the same k/n/bk/bn/mode geometry).

    The packed geometry is immutable (re-packing would defeat the resident-
    weight point), so when it differs from what the calling policy would
    have planned, bk/bn come from the pack and bm — the only free dim left —
    shrinks until the working set honors the *calling* policy's VMEM budget.
    """
    policy = policy if policy is not None else GemmPolicy()
    pln = plan(M, pw.n, pw.k, dtype, policy)
    blk = pln.layout or L.choose_layout(M, pw.n, pw.k, jnp.dtype(dtype),
                                        mode=pw.mode,
                                        vmem_budget=policy.vmem_budget)
    if (blk.bk, blk.bn) != (pw.bk, pw.bn):
        blk = L.BlockLayout(bm=blk.bm, bn=pw.bn, bk=pw.bk, mode=blk.mode)
        itemsize = jnp.dtype(dtype).itemsize
        while (blk.vmem_bytes(itemsize) > policy.vmem_budget
               and blk.bm > L.SUBLANE):
            blk = L.BlockLayout(blk.bm // 2, blk.bn, blk.bk, blk.mode)
        if blk.vmem_bytes(itemsize) > policy.vmem_budget:
            raise ValueError(
                f"PackedWeight geometry (bk={pw.bk}, bn={pw.bn}) cannot fit "
                f"the calling policy's vmem_budget={policy.vmem_budget} even "
                f"at bm={blk.bm}; re-pack the weight under this policy "
                f"(pack_weight(w, policy)) or raise the budget")
    return blk


# Keys that name GEMM right-hand sides in the model parameter trees
# (models/layers.py, models/ssm.py, models/transformer.py).
_PACK_KEYS = frozenset({
    "wq", "wk", "wv", "wo", "wi", "wq_a", "wq_b", "wkv_a", "wkv_b",
    "w_z", "w_x", "w_B", "w_C", "w_dt", "w_out", "head", "router",
})
# MoE expert banks live directly under the "moe" dict and run as grouped
# einsums (E, d_in, d_out) — never pack those. The shared-expert MLP nests
# one level deeper ("moe" → "shared" → "wi") and is a plain linear.
_EINSUM_BANKS = frozenset({"wi", "wo"})


def pack_model_weights(params, policy: Optional[GemmPolicy] = None,
                       *, m_hint: int = 512,
                       quantize: Optional[str] = None):
    """Pack every GEMM weight in a model param tree into a PackedWeight.

    Realizes the paper's offline weight arrangement (Fig. 5): each weight is
    laid out block-major once at model build/load; api.linear consumes the
    blocks directly. Non-GEMM params (norms, biases, conv kernels, embeds,
    MoE expert banks) pass through untouched.

    ``quantize="int8"`` (default: the policy's ``weight_dtype``) makes every
    packed weight a :class:`QuantizedPackedWeight` — the quantize-at-pack
    deployment shape where serving holds int8 blocks + scales resident.
    """
    quantize = quantize if quantize is not None else (
        policy.weight_dtype if policy is not None else None)
    def rec(node, parent_key):
        if isinstance(node, dict):
            return {k: rec(v, k) if isinstance(v, (dict, list))
                    else maybe_pack(parent_key, k, v)
                    for k, v in node.items()}
        if isinstance(node, list):
            return [rec(v, parent_key) for v in node]
        return node

    def maybe_pack(parent_key, key, leaf):
        if key not in _PACK_KEYS or not hasattr(leaf, "ndim"):
            return leaf
        if parent_key == "moe" and key in _EINSUM_BANKS:
            return leaf
        if leaf.ndim < 2:
            return leaf
        return pack_weight(leaf, policy, m_hint=m_hint, quantize=quantize)

    return rec(params, None)
