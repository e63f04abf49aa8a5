"""jit'd dispatch wrappers over the Pallas kernels.

One entry point per kernel, handling:
  * backend policy (real TPU pallas vs CPU interpret vs pure-jnp oracle),
  * the paper's DC/DM access-mode block geometries,
  * layout plumbing (row-major model tensors ↔ kernel-native layouts).

Models call repro.core.api (which routes GEMMs here under the pallas
backends); tests call these directly for shape/dtype sweeps.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import layout as L
from repro.core.plan import on_tpu
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.matrixflow_gemm import (matrixflow_gemm,
                                           matrixflow_gemm_block_major)
from repro.kernels.ssd_scan import ssd_scan


def gemm(a: jax.Array, b: jax.Array, *, mode: str = "dm",
         out_dtype: Optional[jnp.dtype] = None,
         impl: Optional[str] = None) -> jax.Array:
    """MatrixFlow GEMM. impl: None (auto) | 'pallas' | 'interpret' | 'ref'."""
    impl = impl or ("pallas" if on_tpu() else "interpret")
    if impl == "ref":
        return ref.matmul_ref(a, b, out_dtype)
    return matrixflow_gemm(a, b, mode=mode, out_dtype=out_dtype,
                           interpret=(impl == "interpret"))


def gemm_preformatted(a_bm: jax.Array, b_bm: jax.Array, *, blk: L.BlockLayout,
                      out_dtype: Optional[jnp.dtype] = None,
                      impl: Optional[str] = None) -> jax.Array:
    """Deploy path: operands already block-major (weights formatted once at
    load; activations produced block-major by the previous GEMM — Fig. 5)."""
    impl = impl or ("pallas" if on_tpu() else "interpret")
    return matrixflow_gemm_block_major(a_bm, b_bm, blk=blk,
                                       out_dtype=out_dtype,
                                       interpret=(impl == "interpret"))


def mha(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True,
        scale: Optional[float] = None, soft_cap: Optional[float] = None,
        q_positions: Optional[jax.Array] = None,
        kv_valid_len: Optional[jax.Array] = None,
        impl: Optional[str] = None,
        block_q: int = 128, block_k: int = 128) -> jax.Array:
    """Fused attention over (B, S, H, D)-layout tensors (model layout).

    q_positions (B, Sq) / kv_valid_len (B,) carry the decode/serving offset
    and cache-length semantics (see kernels/flash_attention.py). impl 'ref'
    uses the pure-jnp oracle; otherwise the Pallas flash kernel (interpret
    mode off-TPU)."""
    impl = impl or ("pallas" if on_tpu() else "interpret")
    if impl == "ref":
        return ref.mha_ref(q, k, v, causal=causal, scale=scale,
                           soft_cap=soft_cap, q_positions=q_positions,
                           kv_valid_len=kv_valid_len)
    out = flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), q_positions, kv_valid_len,
        causal=causal, scale=scale, soft_cap=soft_cap,
        block_q=block_q, block_k=block_k,
        interpret=(impl == "interpret"))
    return out.transpose(0, 2, 1, 3)


def ssd(x: jax.Array, dt: jax.Array, A: jax.Array, Bc: jax.Array,
        Cc: jax.Array, *, chunk: int = 128,
        impl: Optional[str] = None) -> jax.Array:
    """Chunked SSD scan (B, S, H, P). impl as in mha()."""
    impl = impl or ("pallas" if on_tpu() else "interpret")
    if impl == "ref":
        return ref.ssd_ref(x, dt, A, Bc, Cc)
    return ssd_scan(x, dt, A, Bc, Cc, chunk=chunk,
                    interpret=(impl == "interpret"))
