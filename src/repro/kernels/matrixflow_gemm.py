"""MatrixFlow blocked GEMM as a Pallas TPU kernel (paper Algorithm 1, C2).

The kernel executes the paper's dataflow on the TPU grid:

  grid = (M/bm, N/bn, K/bk), K innermost ("arbitrary"), M/N "parallel"
  A operand : block-major (M/bm, K/bk, bm, bk)   — one contiguous DMA per tile
  B operand : block-major (N/bn, K/bk, bk, bn)   — the paper's horizontal split
  C output  : block-major (M/bm, N/bn, bm, bn)   — written once per (i, j)
  accumulator: VMEM scratch (bm, bn) in int32/fp32 — the paper's Buffer C

Because the operands are stored block-major, each BlockSpec fetch is a single
contiguous HBM region: the Mosaic pipeline issues exactly one DMA descriptor
per tile — the TPU realization of the paper's one-page-one-transaction
property. The double-buffered VMEM windows Pallas maintains for A/B plus the
scratch accumulator are the analogue of the paper's three small local buffers.

Validated on CPU via interpret=True against kernels/ref.py (pure jnp) and
core/blockflow.py (faithful Algorithm-1 rendering).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis.kernel_contracts import (KernelContract, OperandSpec,
                                             Precondition, register_contract,
                                             require)
from repro.core import layout as L


def _acc_dtype(dtype) -> jnp.dtype:
    if jnp.issubdtype(jnp.dtype(dtype), jnp.integer):
        return jnp.dtype(jnp.int32)
    return jnp.dtype(jnp.float32)


# ---------------------------------------------------------------------------
# The dataflow mapping, stated once: these index maps are handed to
# pl.BlockSpec below AND cited by the registered KernelContract, so the
# static checker (repro/analysis/kernel_contracts.py) verifies the very
# callables the kernel executes — coverage, bounds, and the K-revisit
# discipline of the paper's Algorithm 1.
# ---------------------------------------------------------------------------

GEMM_DIMENSION_SEMANTICS = ("parallel", "parallel", "arbitrary")


def _a_index_map(i, j, k):
    return (i, k, 0, 0)


def _b_index_map(i, j, k):
    return (j, k, 0, 0)


def _c_index_map(i, j, k):
    return (i, j, 0, 0)


def _sa_index_map(i, j, k):
    return (i, 0)


def _sb_index_map(i, j, k):
    return (0, j)


def gemm_preconditions(a_shape, b_shape, blk: L.BlockLayout):
    """The kernel's structured entry guards, shared verbatim between the
    runtime ``require`` below and the static contract."""
    nbm, nbk, bm, bk = a_shape
    nbn, nbk2, bk2, bn = b_shape
    return (
        Precondition.check(
            "A/B K-stream agreement",
            (nbk, bk) == (nbk2, bk2),
            f"block-major operands disagree on the K stream: a_bm "
            f"{tuple(a_shape)} walks {nbk} blocks of bk={bk}, b_bm "
            f"{tuple(b_shape)} walks {nbk2} blocks of bk={bk2}"),
        Precondition.check(
            "blocks match layout",
            (bm, bn, bk) == (blk.bm, blk.bn, blk.bk),
            f"operand blocks (bm={bm}, bn={bn}, bk={bk}) do not match the "
            f"BlockLayout (bm={blk.bm}, bn={blk.bn}, bk={blk.bk}); "
            f"re-layout with core.layout.to_block_major_* under this blk"),
    )


@register_contract("matrixflow_gemm")
def gemm_contract(*, a_shape, b_shape, blk: L.BlockLayout,
                  fused: bool = False) -> KernelContract:
    """Contract of :func:`matrixflow_gemm_block_major` for one instance.

    ``a_shape``/``b_shape`` are the block-major operand shapes
    ``(nbm, nbk, bm, bk)`` / ``(nbn, nbk, bk, bn)``; ``fused`` adds the
    W8A8 dequant scale panels. The C output is revisited along grid axis 2
    (the K stream) — the declared reduction axis the checker verifies.
    """
    nbm, nbk, bm, bk = a_shape
    nbn, _, _, bn = b_shape
    operands = [
        OperandSpec("a_bm", "input", (nbm, nbk, 1, 1), (1, 1, bm, bk),
                    _a_index_map),
        OperandSpec("b_bm", "input", (nbn, nbk, 1, 1), (1, 1, bk, bn),
                    _b_index_map),
        OperandSpec("c_bm", "output", (nbm, nbn, 1, 1), (1, 1, bm, bn),
                    _c_index_map, reduction_axes=(2,)),
    ]
    if fused:
        operands += [
            OperandSpec("scale_a", "input", (nbm, 1), (bm, 1),
                        _sa_index_map),
            OperandSpec("scale_b", "input", (1, nbn), (1, bn),
                        _sb_index_map),
        ]
    return KernelContract(
        kernel="matrixflow_gemm",
        grid=(nbm, nbn, nbk),
        operands=tuple(operands),
        dimension_semantics=GEMM_DIMENSION_SEMANTICS,
        preconditions=gemm_preconditions(a_shape, b_shape, blk),
        description="paper Algorithm 1 on the TPU grid (K innermost)")


def _kernel(a_ref, b_ref, o_ref, acc_ref, *, nbk: int, acc_dtype):
    """One grid step: MultiAcc(A[i,k], B[j,k]) into the VMEM accumulator."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[0, 0]            # (bm, bk) — one contiguous MatrixFlow block
    b = b_ref[0, 0]            # (bk, bn)
    acc_ref[...] += jnp.dot(a, b, preferred_element_type=acc_dtype)

    @pl.when(k == nbk - 1)
    def _flush():
        o_ref[0, 0] = acc_ref[...].astype(o_ref.dtype)


def _kernel_fused_dequant(a_ref, b_ref, sa_ref, sb_ref, o_ref, acc_ref, *,
                          nbk: int, acc_dtype):
    """Same schedule, with the W8A8 dequant fused into the C-block flush:
    the finished int32 accumulator is rescaled by the per-row activation
    scale and the per-channel weight scale before the single HBM write
    (core/quant.py's rank-1 dequant — no second pass over C)."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[0, 0]
    b = b_ref[0, 0]
    acc_ref[...] += jnp.dot(a, b, preferred_element_type=acc_dtype)

    @pl.when(k == nbk - 1)
    def _flush():
        scaled = (acc_ref[...].astype(jnp.float32)
                  * sa_ref[...] * sb_ref[...])      # (bm,1)*(1,bn) broadcast
        o_ref[0, 0] = scaled.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("blk", "out_dtype", "interpret", "acc_dtype"),
)
def matrixflow_gemm_block_major(
    a_bm: jax.Array,
    b_bm: jax.Array,
    *,
    blk: L.BlockLayout,
    out_dtype: Optional[jnp.dtype] = None,
    interpret: bool = False,
    acc_dtype: Optional[jnp.dtype] = None,
    scale_a: Optional[jax.Array] = None,
    scale_b: Optional[jax.Array] = None,
) -> jax.Array:
    """C_bm = A_bm @ B_bm over MatrixFlow block-major operands.

    a_bm: (nbm, nbk, bm, bk); b_bm: (nbn, nbk, bk, bn) →
    returns C block-major (nbm, nbn, bm, bn). ``acc_dtype`` overrides the
    default accumulator policy (int → int32, float → fp32) — a GemmPolicy
    knob at the ExecutionPlan layer.

    ``scale_a`` (≤ nbm·bm rows) / ``scale_b`` (≤ nbn·bn channels) switch in
    the dequant-fused kernel for the int8 W8A8 route: each finished int32
    C block is rescaled by ``s_a[m] * s_b[n]`` in VMEM before its single
    HBM write. With scales present the default out_dtype is float32.
    """
    nbm, nbk, bm, bk = a_bm.shape
    nbn, _, _, bn = b_bm.shape
    require(*gemm_preconditions(a_bm.shape, b_bm.shape, blk))
    acc_dtype = jnp.dtype(acc_dtype or _acc_dtype(a_bm.dtype))
    fused = scale_a is not None or scale_b is not None
    out_dtype = jnp.dtype(out_dtype or
                          (jnp.float32 if fused else acc_dtype))

    grid = (nbm, nbn, nbk)
    scratch = [pltpu.VMEM((bm, bn), acc_dtype)]

    in_specs = [
        pl.BlockSpec((1, 1, bm, bk), _a_index_map),
        pl.BlockSpec((1, 1, bk, bn), _b_index_map),
    ]
    operands = [a_bm, b_bm]
    if fused:
        # Scales enter as (M, 1) / (1, N) fp32 panels, zero-padded to the
        # block grid; each tile sees its (bm, 1) / (1, bn) slice.
        sa = (jnp.ones((nbm * bm,), jnp.float32) if scale_a is None
              else jnp.pad(scale_a.astype(jnp.float32),
                           (0, nbm * bm - scale_a.shape[0])))
        sb = (jnp.ones((nbn * bn,), jnp.float32) if scale_b is None
              else jnp.pad(scale_b.astype(jnp.float32),
                           (0, nbn * bn - scale_b.shape[0])))
        in_specs += [
            pl.BlockSpec((bm, 1), _sa_index_map),
            pl.BlockSpec((1, bn), _sb_index_map),
        ]
        operands += [sa.reshape(nbm * bm, 1), sb.reshape(1, nbn * bn)]
        kernel = functools.partial(_kernel_fused_dequant, nbk=nbk,
                                   acc_dtype=acc_dtype)
    else:
        kernel = functools.partial(_kernel, nbk=nbk, acc_dtype=acc_dtype)

    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, bm, bn), _c_index_map),
        out_shape=jax.ShapeDtypeStruct((nbm, nbn, bm, bn), out_dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=GEMM_DIMENSION_SEMANTICS),
        interpret=interpret,
    )
    return call(*operands)


def matrixflow_gemm(
    a: jax.Array,
    b: jax.Array,
    *,
    blk: Optional[L.BlockLayout] = None,
    mode: str = "dm",
    out_dtype: Optional[jnp.dtype] = None,
    interpret: bool = False,
    acc_dtype: Optional[jnp.dtype] = None,
    scale_a: Optional[jax.Array] = None,
    scale_b: Optional[jax.Array] = None,
) -> jax.Array:
    """C = A @ B: re-layout (the paper's data-structure step) + blocked kernel.

    a: (M, K), b: (K, N) row-major. For persistent weights prefer packing
    block-major once (core/plan.py's PackedWeight) — api.linear then calls
    matrixflow_gemm_block_major directly, skipping the per-call re-layout.
    ``scale_a`` (M,) / ``scale_b`` (N,) select the dequant-fused int8 kernel.
    """
    M, K = a.shape
    K2, N = b.shape
    require(Precondition.check(
        "A/B contraction agreement", K == K2,
        f"a has K={K} columns but b has K={K2} rows; C = A @ B needs the "
        f"contraction dims to agree (a {a.shape}, b {b.shape})"))
    if blk is None:
        blk = L.choose_layout(M, N, K, a.dtype, mode=mode)
    a_bm = L.to_block_major_a(a, blk.bm, blk.bk)
    b_bm = L.to_block_major_b(b, blk.bk, blk.bn)
    c_bm = matrixflow_gemm_block_major(
        a_bm, b_bm, blk=blk, out_dtype=out_dtype, interpret=interpret,
        acc_dtype=acc_dtype, scale_a=scale_a, scale_b=scale_b)
    return L.from_block_major_c(c_bm, M, N)
