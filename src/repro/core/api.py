"""Public MatrixFlow API: typed GEMM policies over an extensible registry.

Every GEMM in the model substrate routes through :func:`matmul` /
:func:`linear`. *How* it executes is described by a frozen
:class:`~repro.core.plan.GemmPolicy` — backend, DC/DM access mode, layout
override, accumulator dtype, VMEM budget — resolved per shape into a
memoized :class:`~repro.core.plan.ExecutionPlan` (see repro/core/plan.py).

Built-in backends (registered here; add your own via
:func:`~repro.core.plan.register_backend`):

  "xla"               jnp.dot — distributed dry-run lowering and CPU training
                      (XLA already tiles for the MXU; the MatrixFlow schedule
                      is a kernel-level concern). Consumes batched
                      contractions natively.
  "pallas"            the MatrixFlow Pallas kernel (TPU target; interpret
                      mode where there is no TPU).
  "blockflow"         the faithful Algorithm-1 lax rendering (paper baseline).

The default policy is ``GemmPolicy()`` — backend "auto" (pallas on TPU, xla
elsewhere), access mode "auto" (the sysmodel's analytic DC-vs-DM choice).
Pin a policy for a region with :func:`use_policy`::

    with api.use_policy(GemmPolicy(backend="blockflow", mode="dc")):
        logits = forward(params, cfg, batch)

Weights that persist across calls should be packed block-major once
(:func:`~repro.core.plan.pack_weight` / ``pack_model_weights``) — ``linear``
and ``matmul`` consume :class:`~repro.core.plan.PackedWeight` directly,
realizing the paper's Fig. 5 reuse (no per-call re-layout).
``GemmPolicy(weight_dtype="int8")`` switches weights to the quantized W8A8
route (core/quant.py): int8 blocks + per-channel scales
(:class:`~repro.core.quant.QuantizedPackedWeight`), int32 accumulation,
dequant fused into the C-block flush on the block-major backends.

Attention has the same shape: :func:`attention` routes every model
attention call through an :class:`~repro.core.plan.AttentionPolicy` and its
own backend registry — ``fused`` (the offset-aware flash Pallas kernel,
kernels/flash_attention.py), ``unfused`` (the paper's §4.4 einsum +
host-softmax split), and ``paged`` (the block-table paged-KV kernel,
kernels/paged_attention.py — K/V live in a page pool and a per-request
block table drives the fetch; docs/serving.md). The Pallas kernels run
compiled on a TPU and in interpret mode everywhere else. Pin with
:func:`use_attention_policy`; see docs/attention.md.

Migration from the old stringly-typed API (kept as deprecation shims for one
release): ``gemm_backend("xla")`` → ``use_policy(GemmPolicy(backend="xla"))``;
``matmul(..., mode="dc")`` → ``GemmPolicy(mode="dc")``. See docs/api.md.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import warnings
from typing import Optional, Union

import jax
import jax.numpy as jnp

from repro.core import blockflow
from repro.core import layout as L
from repro.core import plan as P
from repro.core import quant as Q
from repro.core.plan import (  # re-exported: the public policy surface
    GemmPolicy, ExecutionPlan, PackedWeight, QuantizedPackedWeight,
    AttentionPolicy, ShardingPolicy, pack_weight, pack_model_weights,
    plan, plan_cache_info, plan_cache_clear, register_backend,
    unregister_backend, registered_backends,
    register_attention_backend, unregister_attention_backend,
    registered_attention_backends,
)

__all__ = [
    "GemmPolicy", "ExecutionPlan", "PackedWeight", "QuantizedPackedWeight",
    "pack_weight",
    "pack_model_weights", "plan", "plan_cache_info", "plan_cache_clear",
    "register_backend", "unregister_backend", "registered_backends",
    "matmul", "linear", "use_policy", "current_policy", "resolved_backend",
    "prefers_einsum", "gemm_backend", "current_backend",
    "AttentionPolicy", "ShardingPolicy", "attention", "use_attention_policy",
    "current_attention_policy", "resolved_attention_backend",
    "register_attention_backend", "unregister_attention_backend",
    "registered_attention_backends",
]

_state = threading.local()


def current_policy() -> GemmPolicy:
    """The active GemmPolicy (innermost use_policy, else the default)."""
    stack = getattr(_state, "policies", None)
    return stack[-1] if stack else GemmPolicy()


@contextlib.contextmanager
def use_policy(policy: GemmPolicy):
    """Pin the active GEMM policy for the enclosed region (thread-local)."""
    stack = getattr(_state, "policies", None)
    if stack is None:
        stack = _state.policies = []
    stack.append(policy)
    try:
        yield policy
    finally:
        stack.pop()


def resolved_backend(policy: Optional[GemmPolicy] = None) -> str:
    """Registry name the active (or given) policy resolves to."""
    return (policy or current_policy()).resolved_backend()


def current_attention_policy() -> AttentionPolicy:
    """The active AttentionPolicy (innermost use_attention_policy, else the
    default — backend "auto": fused on TPU, unfused elsewhere)."""
    stack = getattr(_state, "attn_policies", None)
    return stack[-1] if stack else AttentionPolicy()


@contextlib.contextmanager
def use_attention_policy(policy: AttentionPolicy):
    """Pin the active attention policy for the enclosed region
    (thread-local, mirrors :func:`use_policy`)."""
    stack = getattr(_state, "attn_policies", None)
    if stack is None:
        stack = _state.attn_policies = []
    stack.append(policy)
    try:
        yield policy
    finally:
        stack.pop()


def resolved_attention_backend(policy: Optional[AttentionPolicy] = None) -> str:
    """Registry name the active (or given) attention policy resolves to."""
    return (policy or current_attention_policy()).resolved_backend()


def prefers_einsum(policy: Optional[GemmPolicy] = None) -> bool:
    """True when the resolved backend consumes batched contractions natively
    (so model code should use einsum instead of the batched 2-D kernel)."""
    return P.get_backend_spec(resolved_backend(policy)).batched


# ---------------------------------------------------------------------------
# Built-in backends
# ---------------------------------------------------------------------------

def _xla_gemm(a, b, pln: ExecutionPlan, out_dtype):
    if isinstance(b, QuantizedPackedWeight):
        aq, sa = Q.quantize_activations(a)
        c = jnp.matmul(aq, b.unpack_quantized(),
                       preferred_element_type=jnp.int32)
        return Q.dequantize_gemm(c, sa, b.scales, out_dtype)
    if isinstance(b, PackedWeight):
        b = b.unpack()
    return jnp.matmul(a, b, preferred_element_type=pln.acc).astype(out_dtype)


def _blockflow_gemm(a2, b, pln: ExecutionPlan, out_dtype):
    if isinstance(b, QuantizedPackedWeight):
        aq, sa = Q.quantize_activations(a2)
        blk = P.layout_for_packed(a2.shape[0], b, jnp.int8, pln.policy)
        return blockflow.block_matmul(
            aq, b.data, blk=blk, b_shape=(b.k, b.n), out_dtype=out_dtype,
            acc_dtype=jnp.int32, scale_a=sa, scale_b=b.scales)
    if isinstance(b, PackedWeight):
        # consume the resident blocks directly — no unpack/re-block round
        # trip (the Fig. 5 reuse property on this backend too)
        blk = P.layout_for_packed(a2.shape[0], b, a2.dtype, pln.policy)
        return blockflow.block_matmul(
            a2, b.data, blk=blk, b_shape=(b.k, b.n), out_dtype=out_dtype,
            acc_dtype=pln.acc)
    return blockflow.block_matmul(a2, b, blk=pln.layout, out_dtype=out_dtype,
                                  acc_dtype=pln.acc)


def _pallas_gemm(a2, b, pln: ExecutionPlan, out_dtype):
    from repro.kernels import matrixflow_gemm as mf  # lazy: pallas import
    interpret = not P.on_tpu()       # compiled on a TPU only
    if isinstance(b, QuantizedPackedWeight):
        aq, sa = Q.quantize_activations(a2)
        blk = P.layout_for_packed(a2.shape[0], b, jnp.int8, pln.policy)
        a_bm = L.to_block_major_a(aq, blk.bm, blk.bk)
        c_bm = mf.matrixflow_gemm_block_major(
            a_bm, b.data, blk=blk, out_dtype=out_dtype,
            interpret=interpret, acc_dtype=jnp.int32,
            scale_a=sa, scale_b=b.scales)
        return L.from_block_major_c(c_bm, a2.shape[0], b.n)
    if isinstance(b, PackedWeight):
        blk = P.layout_for_packed(a2.shape[0], b, a2.dtype, pln.policy)
        a_bm = L.to_block_major_a(a2, blk.bm, blk.bk)
        c_bm = mf.matrixflow_gemm_block_major(
            a_bm, b.data, blk=blk, out_dtype=out_dtype,
            interpret=interpret, acc_dtype=pln.acc)
        return L.from_block_major_c(c_bm, a2.shape[0], b.n)
    return mf.matrixflow_gemm(a2, b, blk=pln.layout, out_dtype=out_dtype,
                              interpret=interpret, acc_dtype=pln.acc)


register_backend("xla", _xla_gemm, batched=True, needs_layout=False)
register_backend("blockflow", _blockflow_gemm)
register_backend("pallas", _pallas_gemm)


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

def matmul(a: jax.Array, b: Union[jax.Array, PackedWeight], *,
           policy: Optional[GemmPolicy] = None,
           out_dtype: Optional[jnp.dtype] = None,
           mode: Optional[str] = None) -> jax.Array:
    """C = A @ B through the plan the active policy resolves to.

    a: (..., M, K); b: (K, N), (..., K, N), or a PackedWeight (resident
    block-major). Output dtype defaults to the promoted input dtype (not the
    accumulator) to keep model code natural.
    """
    pol = policy if policy is not None else current_policy()
    if mode is not None:  # deprecated keyword, one-release shim
        warnings.warn("matmul(mode=...) is deprecated; use "
                      "GemmPolicy(mode=...)", DeprecationWarning,
                      stacklevel=2)
        pol = dataclasses.replace(pol, mode=mode)
    quantized = isinstance(b, QuantizedPackedWeight)
    packed = quantized or isinstance(b, PackedWeight)
    if out_dtype is None:
        if quantized:
            # the route dequantizes back to the weight's original fp dtype
            out_dtype = jnp.promote_types(a.dtype, jnp.dtype(b.dequant_dtype))
        else:
            out_dtype = jnp.promote_types(
                a.dtype, b.data.dtype if packed else b.dtype)
            if jnp.issubdtype(out_dtype, jnp.integer):
                # paper MAC policy: integer GEMMs surface their int32
                # accumulator (an int8 result would truncate, Table 2)
                out_dtype = blockflow.acc_dtype_for(out_dtype)
    spec = P.get_backend_spec(pol.resolved_backend())

    if spec.batched and not packed:
        # native batched contraction (jnp broadcasting semantics)
        M = int(a.size // a.shape[-1]) if a.ndim > 1 else 1
        pln = plan(M, b.shape[-1], a.shape[-1], a.dtype, pol)
        return spec.fn(a, b, pln, out_dtype)

    if not packed and b.ndim != 2:
        # batched rhs: vmap over shared leading dims
        assert a.shape[:-2] == b.shape[:-2], (a.shape, b.shape)
        lead = a.shape[:-2]
        a2 = a.reshape((-1,) + a.shape[-2:])
        b2 = b.reshape((-1,) + b.shape[-2:])
        out = jax.vmap(lambda x, y: matmul(x, y, policy=pol,
                                           out_dtype=out_dtype))(a2, b2)
        return out.reshape(lead + out.shape[-2:])

    # Collapse leading dims to a single M for the 2-D kernels.
    lead = a.shape[:-1]
    a2 = a.reshape(-1, a.shape[-1])
    M, K = a2.shape
    N = b.n if packed else b.shape[-1]
    # Quantized weights execute int8×int8→int32: plan for the int8 problem
    # (sysmodel auto-mode and acc resolution both see the kernel dtype).
    pln = plan(M, N, K, jnp.int8 if quantized else a2.dtype, pol)
    c = spec.fn(a2, b, pln, out_dtype)
    return c.reshape(lead + (N,)).astype(out_dtype)


def linear(x: jax.Array, w: Union[jax.Array, PackedWeight,
                                  QuantizedPackedWeight],
           bias: Optional[jax.Array] = None, *,
           policy: Optional[GemmPolicy] = None,
           mode: Optional[str] = None) -> jax.Array:
    """y = x @ w (+ bias): the layer-level entry point used by models.

    ``w`` may be a PackedWeight — laid out block-major once at model build —
    in which case block-major backends consume the blocks directly; or a
    QuantizedPackedWeight, which runs the int8 W8A8 route (core/quant.py).

    Under ``GemmPolicy(weight_dtype="int8")`` a raw fp weight is quantized
    on the fly (per call — pack once with ``pack_model_weights`` for the
    resident deployment shape). Only ``linear`` applies the knob to raw
    arrays: ``matmul``'s operands include activation×activation contractions
    (attention scores), which stay in their stored dtype.
    """
    pol = policy if policy is not None else current_policy()
    if (pol.weight_dtype is not None
            and getattr(w, "ndim", 0) == 2
            and not isinstance(w, (PackedWeight, QuantizedPackedWeight))
            and jnp.issubdtype(x.dtype, jnp.floating)
            and jnp.issubdtype(w.dtype, jnp.floating)):
        m_hint = max(int(x.size // x.shape[-1]), 1)
        w = P.pack_weight(w, pol, m_hint=m_hint, quantize=pol.weight_dtype)
    y = matmul(x, w, policy=pol, mode=mode)
    if bias is not None:
        y = y + bias
    return y


# ---------------------------------------------------------------------------
# Attention: policy-selectable fused/unfused execution (docs/attention.md)
# ---------------------------------------------------------------------------

def _reject_paged(backend: str, block_tables, kv_scales=None):
    if block_tables is not None:
        raise ValueError(
            f"attention backend {backend!r} cannot consume a paged KV cache "
            f"(got a block table); use AttentionPolicy(backend='paged') — "
            f"docs/serving.md")
    if kv_scales is not None:
        raise ValueError(
            f"attention backend {backend!r} cannot consume a quantized KV "
            f"pool (got kv_scales); use AttentionPolicy(backend='paged', "
            f"kv_dtype='int8') — docs/quant.md#kv-pages")


def _unfused_attention(q, k, v, *, q_positions, kv_valid_len, causal, scale,
                       soft_cap, policy, block_tables=None, kv_scales=None):
    """The einsum + host-softmax baseline (the paper's §4.4 split: GEMMs on
    the accelerator, softmax on the host). GQA via reshape; score/value
    contractions follow the ambient *GEMM* policy — einsum when the resolved
    GEMM backend consumes batched contractions natively, the batched
    MatrixFlow kernel otherwise."""
    _reject_paged("unfused", block_tables, kv_scales)
    B, Sq, H, Dk = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    rep = H // Hkv
    qg = q.reshape(B, Sq, Hkv, rep, Dk)
    if prefers_einsum():
        logits = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k,
                            preferred_element_type=jnp.float32)
    else:  # MatrixFlow path: fold (B,Hkv,rep) into the vmapped batch
        qm = qg.transpose(0, 2, 3, 1, 4).reshape(B * Hkv * rep, Sq, Dk)
        km = (jnp.repeat(k.transpose(0, 2, 1, 3), rep, axis=1)
              .reshape(B * Hkv * rep, T, Dk))
        logits = matmul(qm, km.transpose(0, 2, 1), out_dtype=jnp.float32)
        logits = logits.reshape(B, Hkv, rep, Sq, T)
    logits = logits.astype(jnp.float32) * scale
    if soft_cap:
        logits = soft_cap * jnp.tanh(logits / soft_cap)
    kv_pos = jnp.arange(T)[None, None, :]                     # (1,1,T)
    valid = kv_pos < kv_valid_len[:, None, None]              # (B,1,T)
    if causal:
        valid = valid & (kv_pos <= q_positions[:, :, None])   # (B,Sq,T)
    valid = jnp.broadcast_to(valid, (B, Sq, T))[:, None, None]  # (B,1,1,Sq,T)
    logits = jnp.where(valid, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)                   # host-side op
    # fully-masked rows → zeros (the shared contract with the fused kernel)
    probs = jnp.where(valid, probs, 0.0)
    if prefers_einsum():
        out = jnp.einsum("bgrqk,bkgd->bqgrd", probs.astype(v.dtype), v)
    else:
        pm = probs.reshape(B * Hkv * rep, Sq, T).astype(v.dtype)
        vm = (jnp.repeat(v.transpose(0, 2, 1, 3), rep, axis=1)
              .reshape(B * Hkv * rep, T, v.shape[-1]))
        out = matmul(pm, vm)
        out = (out.reshape(B, Hkv, rep, Sq, v.shape[-1])
               .transpose(0, 3, 1, 2, 4))
    return out.reshape(B, Sq, H, v.shape[-1])


def _fused_mha(q, k, v, *, q_positions, kv_valid_len, causal, scale,
               soft_cap, policy):
    from repro.kernels import ops  # lazy: pallas import; picks the platform
    return ops.mha(q, k, v, causal=causal, scale=scale, soft_cap=soft_cap,
                   q_positions=q_positions, kv_valid_len=kv_valid_len,
                   block_q=policy.block_q, block_k=policy.block_k)


def _fused_attention(q, k, v, *, block_tables=None, kv_scales=None, **kw):
    _reject_paged("fused", block_tables, kv_scales)
    return _fused_mha(q, k, v, **kw)


def _paged_attention(q, k, v, *, q_positions, kv_valid_len, causal, scale,
                     soft_cap, policy, block_tables=None, kv_scales=None):
    """Block-table paged flash attention (kernels/paged_attention.py).

    With a block table, k/v are the page pools (P, page_size, Hkv, D) and
    the table drives the kernel's BlockSpec index maps — int8 pools
    additionally carry ``kv_scales`` (per-page-per-head fp32), which the
    kernel dequantizes in its K/V-block fetch. Without a block table —
    cache-less training/scoring, or an MLA latent cache that stays
    contiguous — the operands are dense and the paged policy degrades to
    the fused flash kernel (identical contract), so a single policy covers
    a model end to end.
    """
    if block_tables is None:
        _reject_paged("paged", None, kv_scales)
        return _fused_mha(q, k, v, q_positions=q_positions,
                          kv_valid_len=kv_valid_len, causal=causal,
                          scale=scale, soft_cap=soft_cap, policy=policy)
    from repro.kernels import paged_attention as PA  # lazy: pallas
    return PA.paged_attention(
        q, k, v, block_tables, q_positions, kv_valid_len,
        kv_scales=kv_scales, causal=causal, scale=scale, soft_cap=soft_cap,
        block_q=policy.block_q, interpret=not P.on_tpu())


register_attention_backend("unfused", _unfused_attention)
register_attention_backend("fused", _fused_attention)
register_attention_backend("paged", _paged_attention)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              q_positions: jax.Array,
              kv_valid_len: jax.Array,
              causal: bool = True,
              scale: Optional[float] = None,
              soft_cap: Optional[float] = None,
              block_tables: Optional[jax.Array] = None,
              kv_scales=None,
              policy: Optional[AttentionPolicy] = None) -> jax.Array:
    """Scaled-dot-product attention through the active AttentionPolicy.

    Model-layout operands: q (B,Sq,H,Dk), k (B,T,Hkv,Dk), v (B,T,Hkv,Dv),
    with GQA/MQA expressed by Hkv dividing H. Every backend implements one
    contract (see kernels/ref.py::mha_ref): key j of batch row b is visible
    to query i iff ``j < kv_valid_len[b]`` and, when causal,
    ``j <= q_positions[b, i]``; query rows with no visible key — serving's
    masked position −1 slots — return zeros.

    q_positions: (B, Sq) absolute positions of the queries (int32).
    kv_valid_len: (B,) populated keys/cache slots per batch row.
    block_tables: (B, n_blocks) int32 — only with the ``paged`` backends,
    where k/v are page pools (P, page_size, Hkv, D) and the table maps each
    row's logical key blocks to physical pages (docs/serving.md). Dense
    backends reject a non-None block table.
    kv_scales: ((P, Hkv), (P, Hkv)) fp32 — only with ``paged`` backends
    whose pools are int8 (AttentionPolicy.kv_dtype='int8'); the per-page-
    per-head K and V scales the kernel dequantizes with
    (docs/quant.md#kv-pages). Dense backends reject non-None kv_scales.
    """
    pol = policy if policy is not None else current_attention_policy()
    spec = P.get_attention_backend_spec(pol.resolved_backend())
    if scale is None:
        scale = q.shape[-1] ** -0.5
    # block_tables/kv_scales are forwarded only when present: backends
    # registered before the paged subsystem (without the kwargs) keep
    # working for every dense call, and a paged call against one fails
    # loudly on the kwarg.
    kwargs = ({"block_tables": block_tables} if block_tables is not None
              else {})
    if kv_scales is not None:
        kwargs["kv_scales"] = kv_scales
    return spec.fn(q, k, v, q_positions=q_positions,
                   kv_valid_len=kv_valid_len, causal=causal, scale=scale,
                   soft_cap=soft_cap, policy=pol, **kwargs)


# ---------------------------------------------------------------------------
# Deprecation shims (one release): the old stringly-typed surface
# ---------------------------------------------------------------------------

def current_backend() -> str:
    """Deprecated: use current_policy() / resolved_backend()."""
    return resolved_backend()


@contextlib.contextmanager
def gemm_backend(name: str):
    """Deprecated context manager: pin by backend name.

    Use ``use_policy(GemmPolicy(backend=name))`` instead (docs/api.md has
    the migration table).
    """
    warnings.warn("gemm_backend(name) is deprecated; use "
                  "use_policy(GemmPolicy(backend=name))", DeprecationWarning,
                  stacklevel=3)
    with use_policy(GemmPolicy(backend=name)) as pol:
        yield pol
