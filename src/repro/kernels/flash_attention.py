"""Fused (flash) attention as a Pallas TPU kernel — beyond-paper optimization.

The paper keeps softmax on the CPU and runs QKᵀ / PV as separate accelerator
GEMMs (§4.4), and measures 13.3 % non-GEMM + 24.25 % control overhead left
on the table (§4.5). On TPU we can close that gap by fusing the whole
attention inner loop into one kernel: the MatrixFlow insight (stream
page/block-sized operand tiles through the systolic datapath, never spill
the intermediate) applies directly — the (bq × bk) score tile lives only in
VMEM, exactly like the paper's Buffer C, and is consumed by the online
softmax before the next block arrives.

Layout: grid = (B, H, nQ, nK), K innermost ("arbitrary" = sequential), with
running max / denominator / output accumulator in VMEM scratch (the flash
recurrence). GQA is expressed in the BlockSpec index map (kv head = h//rep),
so no repeated K/V materialization in HBM — the MatrixFlow-style "fetch the
block you need, once" property.

Decode/serving semantics (the offset-aware extension):

  * ``q_positions`` (B, Sq) gives each query row its absolute sequence
    position. Causal masking compares key index against *that* position, so
    a single query (Sq=1) against a long KV cache attends exactly its
    prefix. The default — ``arange(Sq) + (Sk - Sq)`` — is bottom-right
    aligned, matching :func:`repro.kernels.ref.mha_ref`.
  * ``kv_valid_len`` (B,) bounds the populated keys per batch row: padded /
    not-yet-written cache slots contribute exactly zero weight, causal or
    not (this replaces the old ``Sk % block_k == 0`` ValueError for ragged
    non-causal keys).
  * A query row with *no* valid key (e.g. the serving engine's masked
    position −1 slots) produces an all-zero output row — deterministic and
    finite, never NaN.

Key blocks entirely outside a row-block's reach (beyond the causal frontier
or past every row's valid length) are skipped at runtime — decode against a
mostly-empty cache touches only the populated blocks.

Validated in interpret mode against kernels/ref.py::mha_ref.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis.kernel_contracts import (KernelContract, OperandSpec,
                                             Precondition, register_contract,
                                             require)

NEG_INF = -1e30

# ---------------------------------------------------------------------------
# The dataflow mapping, stated once: these index maps are handed to
# pl.BlockSpec below AND cited by the registered KernelContract, so the
# static checker verifies the very callables the kernel executes. The
# trailing ``*_`` takes the scalar-prefetch ref (kv_valid_len), which no map
# reads; the checker calls them with the grid indices alone.
# ---------------------------------------------------------------------------

ATTN_DIMENSION_SEMANTICS = ("parallel", "parallel", "parallel", "arbitrary")


def _qpos_index_map(b, h, i, j, *_):
    return (b, i, 0)


def _q_index_map(b, h, i, j, *_):
    return (b, h, i, 0)


def _make_kv_index_map(rep: int):
    """K/V fetch under GQA: query head h reads kv head h // rep — the
    BlockSpec expression of grouped heads (no HBM repeat)."""
    def _kv_index_map(b, h, i, j, *_):
        return (b, h // rep, j, 0)
    return _kv_index_map


def _o_index_map(b, h, i, j, *_):
    return (b, h, i, 0)


def attention_preconditions(H: int, Hkv: int):
    """Structured entry guards shared between the runtime ``require`` and
    the static contract."""
    return (
        Precondition.check(
            "GQA head divisibility", Hkv > 0 and H % Hkv == 0,
            f"H={H} query heads must be an integer multiple of Hkv={Hkv} "
            f"kv heads (GQA groups of H // Hkv); got remainder "
            f"{H % Hkv if Hkv else 'undefined'}"),
    )


@register_contract("flash_attention")
def flash_attention_contract(*, B, H, Hkv, Sq, Sk, D, Dv,
                             block_q: int = 128,
                             block_k: int = 128) -> KernelContract:
    """Contract of :func:`flash_attention` for one logical shape.

    Mirrors the kernel's own derivation: bq/bk clamp to Sq/Sk, the padded
    extents round up to block multiples, and the output o is revisited
    along grid axis 3 (the key stream) — the declared reduction axis.
    K/V coverage under GQA is partial by construction (each kv head is
    fetched rep times; every (b, hkv, j) block is still touched).
    ``kv_valid_len`` is a scalar-prefetch operand (SMEM, read whole), so it
    has no block geometry to check.
    """
    rep = H // Hkv if Hkv and H % Hkv == 0 else 1
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    pq, pk = (-Sq) % bq, (-Sk) % bk
    nq, nk = (Sq + pq) // bq, (Sk + pk) // bk
    kv_map = _make_kv_index_map(rep)
    operands = (
        OperandSpec("q_positions", "input", (B, nq, 1), (1, bq, 1),
                    _qpos_index_map, expected_blocks=None),
        OperandSpec("q", "input", (B, H, nq, 1), (1, 1, bq, D),
                    _q_index_map),
        OperandSpec("k", "input", (B, Hkv, nk, 1), (1, 1, bk, D),
                    kv_map),
        OperandSpec("v", "input", (B, Hkv, nk, 1), (1, 1, bk, Dv),
                    kv_map),
        OperandSpec("o", "output", (B, H, nq, 1), (1, 1, bq, Dv),
                    _o_index_map, reduction_axes=(3,)),
    )
    return KernelContract(
        kernel="flash_attention",
        grid=(B, H, nq, nk),
        operands=operands,
        dimension_semantics=ATTN_DIMENSION_SEMANTICS,
        preconditions=attention_preconditions(H, Hkv),
        description="fused online-softmax attention, K innermost")


# ---------------------------------------------------------------------------
# The streaming-softmax recurrence, shared by the fused and paged kernels
# (kernels/paged_attention.py). The two kernels differ ONLY in where a key
# block comes from — contiguous cache layout vs a block-table page fetch —
# never in these numerics: the masked-row zero contract, the fp32 online
# softmax, and the l==0 flush guard must stay bit-identical across them.
# ---------------------------------------------------------------------------

def attention_block_init(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def attention_block_step(q, k, v, cols, qpos, kvlen, m_ref, l_ref, acc_ref,
                         *, scale: float, causal: bool,
                         soft_cap: Optional[float]):
    """One online-softmax step over a key block.

    q (bq, d); k (bk, d); v (bk, dv); cols (bq, bk) — the *logical* key
    positions of this block (a paged caller derives them from the logical
    block index, not the physical page); qpos (bq, 1); kvlen scalar;
    m/l/acc are the VMEM scratch of the flash recurrence.
    """
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale       # (bq, bk)
    if soft_cap:
        s = soft_cap * jnp.tanh(s / soft_cap)
    valid = cols < kvlen                                  # KV length mask
    if causal:
        valid = jnp.logical_and(valid, cols <= qpos)      # per-row offset
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_ref[...]                                   # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    # p is zeroed where invalid (not just -inf-masked): for a fully
    # masked row m_new stays NEG_INF and exp(s - m_new) would be 1.
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)         # (bq, bk)
    corr = jnp.exp(m_prev - m_new)                        # (bq, 1)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def attention_block_flush(l_ref, acc_ref, dtype):
    """l == 0 (no valid key anywhere) → zero output row, not NaN."""
    return (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(dtype)


def _kernel(kvlen_ref, qpos_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, soft_cap: Optional[float],
            bq: int, bk: int, nk: int):
    b = pl.program_id(0)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        attention_block_init(m_ref, l_ref, acc_ref)

    qpos = qpos_ref[0]                                    # (bq, 1) int32
    kvlen = kvlen_ref[b]                                  # scalar int32
    # Skip key blocks no row of this q block can see: past every valid key,
    # or (causal) strictly in the future of the furthest query position.
    run = ik * bk < kvlen
    if causal:
        run = jnp.logical_and(run, ik * bk <= jnp.max(qpos))

    @pl.when(run)
    def _step():
        cols = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        attention_block_step(q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], cols,
                             qpos, kvlen, m_ref, l_ref, acc_ref,
                             scale=scale, causal=causal, soft_cap=soft_cap)

    @pl.when(ik == nk - 1)
    def _flush():
        o_ref[0, 0] = attention_block_flush(l_ref, acc_ref, o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "soft_cap", "block_q", "block_k",
                     "interpret"),
)
def flash_attention(
    q: jax.Array,             # (B, H, Sq, D)
    k: jax.Array,             # (B, Hkv, Sk, D)
    v: jax.Array,             # (B, Hkv, Sk, Dv)
    q_positions: Optional[jax.Array] = None,   # (B, Sq) int32; <0 → masked
    kv_valid_len: Optional[jax.Array] = None,  # (B,) int32; None → Sk
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    soft_cap: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, H, Sq, D = q.shape
    _, Hkv, Sk, Dv = v.shape
    require(*attention_preconditions(H, Hkv))
    rep = H // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    if q_positions is None:
        # bottom-right aligned (mha_ref's tril(k=Sk-Sq)); == arange for Sq==Sk
        q_positions = jnp.broadcast_to(
            jnp.arange(Sq, dtype=jnp.int32) + (Sk - Sq), (B, Sq))
    q_positions = q_positions.astype(jnp.int32)
    if kv_valid_len is None:
        kv_valid_len = jnp.full((B,), Sk, jnp.int32)
    kv_valid_len = jnp.minimum(kv_valid_len.astype(jnp.int32), Sk)

    # pad S to block multiples; padded queries carry position -1 (fully
    # masked → zero rows, sliced off below) and padded keys sit at indices
    # >= Sk >= kv_valid_len (zero weight via the KV length mask).
    pq = (-Sq) % bq
    pk = (-Sk) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
        q_positions = jnp.pad(q_positions, ((0, 0), (0, pq)),
                              constant_values=-1)
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    Sq_p, Sk_p = Sq + pq, Sk + pk
    nq, nk = Sq_p // bq, Sk_p // bk

    # (B, Sq_p, 1) so the kernel reads a (bq, 1) tile that broadcasts
    # directly against the (bq, bk) score tile. The per-row valid length is
    # scalar-prefetched into SMEM: a (1, 1) VMEM block of a (B, 1) array
    # breaks the TPU's (8, 128) block-tiling rule.
    qpos_in = q_positions[..., None]

    kernel = functools.partial(_kernel, scale=scale, causal=causal,
                               soft_cap=soft_cap, bq=bq, bk=bk, nk=nk)
    kv_index_map = _make_kv_index_map(rep)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,              # kv_valid_len
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, 1), _qpos_index_map),
            pl.BlockSpec((1, 1, bq, D), _q_index_map),
            pl.BlockSpec((1, 1, bk, D), kv_index_map),
            pl.BlockSpec((1, 1, bk, Dv), kv_index_map),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, Dv), _o_index_map),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Sq_p, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=ATTN_DIMENSION_SEMANTICS),
        interpret=interpret,
    )(kv_valid_len, qpos_in, q, k, v)
    return out[:, :, :Sq]
