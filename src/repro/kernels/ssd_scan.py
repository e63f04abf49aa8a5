"""Mamba-2 SSD chunked scan as a Pallas TPU kernel.

The SSD "state-space duality" (arXiv:2405.21060) decomposes the linear
recurrence into block-matrix GEMMs — structurally the same move as the
paper's Algorithm 1 (stream block operands, accumulate block outputs). This
kernel maps it onto the TPU grid:

  grid = (B, H, n_chunks); the chunk axis is sequential ("arbitrary") and
  carries the running (P × N) state in VMEM scratch — the direct analogue of
  the paper's Buffer-C accumulator that lives on-accelerator across the
  K-stream. Per chunk, all heavy ops are MXU matmuls:

    CBᵀ  : (Q,N)@(N,Q)    intra-chunk scores
    ·L   : causal decay mask (elementwise, VPU)
    @dtx : (Q,Q)@(Q,P)    intra-chunk output
    Cᵀh  : (Q,N)@(N,P)    inter-chunk contribution from carried state
    Bᵀx  : (N,Q)@(Q,P)    state update GEMM

Validated in interpret mode against kernels/ref.py::ssd_ref and
models/ssm.py::ssd_chunked.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis.kernel_contracts import (KernelContract, OperandSpec,
                                             Precondition, register_contract)


# ---------------------------------------------------------------------------
# The dataflow mapping, stated once: handed to pl.BlockSpec below AND cited
# by the registered KernelContract. The chunk axis carries the (P, N) state
# scratch, so the contract declares it sequential even though no output
# block is revisited along it.
# ---------------------------------------------------------------------------

SSD_DIMENSION_SEMANTICS = ("parallel", "parallel", "arbitrary")


def _x_index_map(b, h, k):
    return (b, h, k, 0, 0)


def _a_index_map(b, h, k):
    return (b, h, k, 0, 0)


def _bc_index_map(b, h, k):
    # B/C are shared across heads: fetched once per (batch, chunk)
    return (b, k, 0, 0)


def _y_index_map(b, h, k):
    return (b, h, k, 0, 0)


def ssd_chunk_size(S: int, chunk: int) -> int:
    """The kernel's chunk derivation: clamp to S, then shrink until the
    sequence divides evenly (the contract's divisibility precondition is
    satisfied by construction — this is where)."""
    Q = min(chunk, S)
    while S % Q:
        Q -= 1
    return Q


@register_contract("ssd_scan")
def ssd_contract(*, B, S, H, P, N, chunk: int = 128) -> KernelContract:
    """Contract of :func:`ssd_scan` for one logical shape.

    No output block is revisited (each (b, h, k) writes its own chunk), but
    the chunk axis threads the carried (P, N) state through VMEM scratch —
    ``sequential_axes=(2,)`` makes the checker reject a "parallel"
    declaration there (the recurrence would be reordered).
    """
    Q = ssd_chunk_size(S, chunk)
    nc = S // Q
    operands = (
        OperandSpec("x", "input", (B, H, nc, 1, 1), (1, 1, 1, Q, P),
                    _x_index_map),
        OperandSpec("a", "input", (B, H, nc, 1, 1), (1, 1, 1, 1, Q),
                    _a_index_map),
        OperandSpec("Bc", "input", (B, nc, 1, 1), (1, 1, Q, N),
                    _bc_index_map),
        OperandSpec("Cc", "input", (B, nc, 1, 1), (1, 1, Q, N),
                    _bc_index_map),
        OperandSpec("y", "output", (B, H, nc, 1, 1), (1, 1, 1, Q, P),
                    _y_index_map),
    )
    return KernelContract(
        kernel="ssd_scan",
        grid=(B, H, nc),
        operands=operands,
        dimension_semantics=SSD_DIMENSION_SEMANTICS,
        sequential_axes=(2,),
        preconditions=(
            Precondition.check(
                "chunk divides sequence", S % Q == 0,
                f"derived chunk Q={Q} does not divide S={S}; "
                f"ssd_chunk_size guarantees this by construction"),
        ),
        description="Mamba-2 SSD chunked scan, state carried along chunks")


def _kernel(x_ref, a_ref, b_ref, c_ref, o_ref, state_ref, *, nc: int, Q: int):
    """One (batch, head, chunk) step. Block shapes:
    x (1,1,1,Q,P) pre-scaled by dt; a (1,1,1,1,Q) per-step log-decay dt·A
    as a lane row; b/c (1,1,Q,N); carried state scratch (P,N) fp32."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0, 0].astype(jnp.float32)       # (Q, P)  = dt_j * x_j
    a = a_ref[0, 0, 0].astype(jnp.float32)       # (1, Q)
    bmat = b_ref[0, 0].astype(jnp.float32)       # (Q, N)
    cmat = c_ref[0, 0].astype(jnp.float32)       # (Q, N)

    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    causal = jj <= ii
    # In-chunk cumulative sum as a lower-triangular matmul (the TPU
    # compiler has no cumsum lowering), once as a column and once as a row:
    # cum_i = sum_{j <= i} a_j.
    lower = causal.astype(jnp.float32)
    nt = (((1,), (1,)), ((), ()))
    hi = jax.lax.Precision.HIGHEST
    cum_col = jax.lax.dot_general(lower, a, nt, precision=hi,
                                  preferred_element_type=jnp.float32)  # (Q,1)
    cum_row = jax.lax.dot_general(a, lower, nt, precision=hi,
                                  preferred_element_type=jnp.float32)  # (1,Q)
    total = jnp.sum(a, axis=1, keepdims=True)    # (1, 1) = cum_{Q-1}
    # L[i,j] = exp(cum_i - cum_j) for j <= i  (segment-sum decay)
    Lmask = jnp.where(causal, jnp.exp(cum_col - cum_row), 0.0)

    # intra-chunk: Y = (L ∘ C Bᵀ) (dt x)
    cb = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q,Q)
    y_intra = jax.lax.dot_general(Lmask * cb, x, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # inter-chunk: Y += diag(exp(cum)) C h_prevᵀ        h_prev: (P,N)
    y_inter = jax.lax.dot_general(
        cmat, state_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * jnp.exp(cum_col)

    # state update: h = exp(sum a) h_prev + (decay_end ∘ x)ᵀ B
    decay_end = jnp.exp(total - cum_col)         # (Q, 1)
    xw = x * decay_end                           # (Q, P)
    dstate = jax.lax.dot_general(xw, bmat, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (P,N)
    state_ref[...] = state_ref[...] * jnp.exp(total) + dstate

    o_ref[0, 0, 0] = (y_intra + y_inter).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    x: jax.Array,      # (B, S, H, P)
    dt: jax.Array,     # (B, S, H)  (softplus-ed step sizes)
    A: jax.Array,      # (H,)       negative decay rates
    Bc: jax.Array,     # (B, S, N)
    Cc: jax.Array,     # (B, S, N)
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Pallas SSD: returns y (B, S, H, P). Head-major grid; B/C shared
    across heads via the BlockSpec index map (fetched once per (b, chunk))."""
    B, S, H, P = x.shape
    N = Bc.shape[-1]
    Q = ssd_chunk_size(S, chunk)
    nc = S // Q

    # pre-scale x by dt and form per-step log-decay a = dt * A
    dtx = (x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None])
    a = dt.astype(jnp.float32) * A[None, None, :]

    # head-major layouts: (B, H, nc, Q, ·)
    xq = dtx.reshape(B, nc, Q, H, P).transpose(0, 3, 1, 2, 4)
    # a as a (1, Q) lane row per chunk: a (1, Q) block of (nc, Q) would
    # break the TPU's (8, 128) block-tiling rule on the chunk axis
    aq = a.reshape(B, nc, Q, H).transpose(0, 3, 1, 2)[:, :, :, None, :]
    bq = Bc.astype(jnp.float32).reshape(B, nc, Q, N)
    cq = Cc.astype(jnp.float32).reshape(B, nc, Q, N)

    grid = (B, H, nc)
    kernel = functools.partial(_kernel, nc=nc, Q=Q)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), _x_index_map),
            pl.BlockSpec((1, 1, 1, 1, Q), _a_index_map),
            pl.BlockSpec((1, 1, Q, N), _bc_index_map),
            pl.BlockSpec((1, 1, Q, N), _bc_index_map),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, Q, P), _y_index_map),
        out_shape=jax.ShapeDtypeStruct((B, H, nc, Q, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=SSD_DIMENSION_SEMANTICS),
        interpret=interpret,
    )(xq, aq, bq, cq)
    # (B, H, nc, Q, P) → (B, S, H, P)
    return out.reshape(B, H, S, P).transpose(0, 2, 1, 3)
