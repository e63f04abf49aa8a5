"""Paged (block-table) flash attention as a Pallas TPU kernel.

The serving engine's largest tensor is the KV cache, and a contiguous
``(batch_slots, max_len)`` slab violates the paper's core discipline —
stream page/block-sized operand tiles and never materialize the worst case
(§4.3–4.4). This kernel closes that gap: K/V live in a **page pool** of
fixed ``page_size``-token pages (``serving/kv_pool.py``) and each request
owns a **block table** mapping its logical key blocks to physical pages.
The block table drives the BlockSpec index maps through Pallas scalar
prefetch (``pltpu.PrefetchScalarGridSpec``): grid step ``(b, h, i, j)``
DMA-fetches physical page ``block_tables[b, j]`` — the MatrixFlow "fetch
exactly the block you need" property, applied to the KV cache.

Everything else is PR 3's offset-aware flash recurrence, unchanged:

  * the logical position of page-``j`` slot ``t`` is ``j * page_size + t``,
    so ``q_positions`` (per-row absolute query positions, −1 → masked row)
    and ``kv_valid_len`` (populated cache slots per row) mask *logical*
    key indices exactly as ``kernels/flash_attention.py`` does — one kernel
    covers paged prefill, paged decode, and GQA (kv head = h // rep in the
    index map);
  * key blocks past a row's valid length or causal frontier are skipped at
    runtime, so decode against a mostly-empty pool touches only the
    populated pages;
  * a fully masked query row produces exactly zeros, never NaN.

Unallocated block-table entries must simply be *valid* page indices (the
engine leaves them at 0): the length mask already gives their keys zero
weight, so the fetched bytes are dead — they only have to be fetchable.

The offset/mask semantics buy speculative decoding for free: the
engine's verify pass (docs/serving.md#speculative-decoding) runs this
same kernel at ``Sq = 1 + k`` with ``q_positions`` starting at the
slot's current offset (−1 padding for unused rows), scoring a pending
token plus ``k`` drafted tokens in one call — chunked prefill, plain
decode, and speculative verify are all just different ``(Sq,
q_positions)`` shapes of one contract.

Validated in interpret mode against kernels/ref.py::mha_ref (the pool is
gathered back to a dense cache for the oracle) in tests/parity.py and
tests/test_paged_attention.py.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis.kernel_contracts import (KernelContract, OperandSpec,
                                             Precondition, register_contract,
                                             require)
from repro.kernels.flash_attention import (ATTN_DIMENSION_SEMANTICS,
                                           attention_block_flush,
                                           attention_block_init,
                                           attention_block_step)


# ---------------------------------------------------------------------------
# The dataflow mapping, stated once. All maps take the scalar-prefetch
# signature (b, h, i, j, bt, kvl); the registered contract binds a concrete
# block table and hands the checker the very callables pallas_call runs.
#
# The kernel sees heads folded into the lane axis: q/o as (B, Sq, H*D) and
# the pools as (P, ps, Hkv*D) — free reshapes of the model and pool
# layouts. Head h is then the h-th D-wide lane block, so every block's last
# two dims are (rows, D): legal under the TPU's (8, 128) tiling rule when D
# is a multiple of 128, where a (.., 1, D) head slice on the second-minor
# axis is not.
# ---------------------------------------------------------------------------

def _pg_qpos_index_map(b, h, i, j, bt, kvl):
    return (b, i, 0)


def _pg_q_index_map(b, h, i, j, bt, kvl):
    return (b, i, h)


def _make_page_index_map(rep: int):
    """The paged indirection: logical key block j of batch row b lives in
    physical page ``bt[b, j]``; GQA folds the query head to kv head
    h // rep. The block-table entry IS the index."""
    def _page_index_map(b, h, i, j, bt, kvl):
        return (bt[b, j], 0, h // rep)
    return _page_index_map


def _page_scale_index_map(b, h, i, j, bt, kvl):
    """Each int8 page's (1, 1, Hkv) fp32 scale row rides the same
    indirection; the kernel picks the kv head's lane."""
    return (bt[b, j], 0, 0)


def _pg_o_index_map(b, h, i, j, bt, kvl):
    return (b, i, h)


def paged_preconditions(H, Hkv, k_pages_shape, v_pages_shape, nb, *,
                        interpret: bool = False):
    """Structured entry guards shared between runtime and static checker."""
    P, ps, Hkv_v = v_pages_shape[0], v_pages_shape[1], v_pages_shape[2]
    D, Dv = k_pages_shape[-1], v_pages_shape[-1]
    return (
        Precondition.check(
            "GQA head divisibility", Hkv > 0 and H % Hkv == 0,
            f"H={H} query heads must be an integer multiple of Hkv={Hkv} "
            f"kv heads"),
        Precondition.check(
            "K/V pool agreement",
            tuple(k_pages_shape[:3]) == (P, ps, Hkv_v),
            f"k_pages {tuple(k_pages_shape)} and v_pages "
            f"{tuple(v_pages_shape)} disagree on (P, page_size, Hkv); the "
            f"pools must be allocated as one paged cache"),
        Precondition.check(
            "TPU lane tiling",
            interpret or (D % 128 == 0 and Dv % 128 == 0) or H == Hkv == 1,
            f"head dims D={D}, Dv={Dv} with H={H}, Hkv={Hkv}: compiled for "
            f"a TPU, heads fold into the lane axis in D-wide blocks, which "
            f"must be multiples of 128 unless there is a single head "
            f"(a head-major pool lifts this, ROADMAP Speed 3)"),
        Precondition.check(
            "populated block table", nb > 0,
            f"block table has {nb} blocks: the grid's key axis would have "
            f"zero extent and the flush step would never run (the caller "
            f"must short-circuit nb == 0 to zeros)"),
    )


@register_contract("paged_attention")
def paged_attention_contract(*, B, Sq, H, Hkv, D, Dv, P, page_size,
                             block_tables, block_q: int = 128,
                             quantized: bool = False,
                             interpret: bool = False) -> KernelContract:
    """Contract of :func:`paged_attention` for one concrete block table.

    ``block_tables`` is the actual (B, nb) int array: the checker evaluates
    the kernel's scalar-prefetch index maps against it, so out-of-range
    page indices surface as bounds violations and pool coverage narrows to
    exactly the pages the table references (distractor pages are dead by
    design). Output o is revisited along grid axis 3 (the key stream).
    ``interpret`` lifts the TPU's lane-tiling precondition.
    """
    bt = np.asarray(block_tables, dtype=np.int64)
    nb = bt.shape[1] if bt.ndim == 2 else 0
    rep = H // Hkv if Hkv and H % Hkv == 0 else 1
    ps = page_size
    bq = min(block_q, Sq)
    nq = (Sq + (-Sq) % bq) // bq
    page_map = _make_page_index_map(rep)

    def bind(m):
        # close over the concrete table, exactly like PrefetchScalarGridSpec
        return lambda b, h, i, j: m(b, h, i, j, bt, None)

    referenced = frozenset(
        (int(bt[b, j]), 0, hk)
        for b in range(bt.shape[0]) for j in range(nb)
        for hk in range(Hkv))
    operands = [
        OperandSpec("q_positions", "input", (B, nq, 1), (1, bq, 1),
                    bind(_pg_qpos_index_map)),
        OperandSpec("q", "input", (B, nq, H), (1, bq, D),
                    bind(_pg_q_index_map)),
        OperandSpec("k_pages", "input", (P, 1, Hkv), (1, ps, D),
                    bind(page_map), expected_blocks=referenced),
        OperandSpec("v_pages", "input", (P, 1, Hkv), (1, ps, Dv),
                    bind(page_map), expected_blocks=referenced),
        OperandSpec("o", "output", (B, nq, H), (1, bq, Dv),
                    bind(_pg_o_index_map), reduction_axes=(3,)),
    ]
    if quantized:
        scale_blocks = frozenset((p, 0, 0) for (p, _z, _hk) in referenced)
        operands += [
            OperandSpec("k_scales", "input", (P, 1, 1), (1, 1, Hkv),
                        bind(_page_scale_index_map),
                        expected_blocks=scale_blocks),
            OperandSpec("v_scales", "input", (P, 1, 1), (1, 1, Hkv),
                        bind(_page_scale_index_map),
                        expected_blocks=scale_blocks),
        ]
    k_shape = (P, ps, Hkv, D)
    v_shape = (P, ps, Hkv, Dv)
    return KernelContract(
        kernel="paged_attention",
        grid=(B, H, nq, nb),
        operands=tuple(operands),
        dimension_semantics=ATTN_DIMENSION_SEMANTICS,
        preconditions=paged_preconditions(H, Hkv, k_shape, v_shape, nb,
                                          interpret=interpret),
        description="block-table paged flash attention (scalar prefetch)")


def _kernel(bt_ref, kvlen_ref, qpos_ref, q_ref, k_ref, v_ref, *rest,
            scale: float, causal: bool, soft_cap: Optional[float],
            bq: int, ps: int, nb: int, rep: int, quantized: bool):
    # rest is [ks_ref, vs_ref,] o_ref, m_ref, l_ref, acc_ref — the scale
    # operands exist only on the int8 path (pallas passes refs positionally
    # in in_specs order, then outputs, then scratch).
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    hk = pl.program_id(1) // rep                          # kv head
    ij = pl.program_id(3)                                 # logical key block

    @pl.when(ij == 0)
    def _init():
        attention_block_init(m_ref, l_ref, acc_ref)

    qpos = qpos_ref[0]                                    # (bq, 1) int32
    kvlen = kvlen_ref[b]                                  # scalar int32
    # Skip logical key blocks no row of this q block can see: past every
    # valid key, or (causal) strictly beyond the furthest query position.
    run = ij * ps < kvlen
    if causal:
        run = jnp.logical_and(run, ij * ps <= jnp.max(qpos))

    @pl.when(run)
    def _step():
        # cols are LOGICAL key positions: the block table only redirects the
        # physical fetch (this kernel's BlockSpec index maps), never the
        # masking arithmetic — the numerics are flash_attention.py's
        # recurrence, shared verbatim.
        cols = ij * ps + jax.lax.broadcasted_iota(jnp.int32, (bq, ps), 1)
        kblk = k_ref[0]
        vblk = v_ref[0]
        if quantized:
            # dequantize the int8 page in-register: the HBM→VMEM stream
            # stayed int8, the recurrence below runs fp32 as always. The
            # scale block is this page's (1, Hkv) row; a lane mask picks
            # this kv head's scale without a dynamic lane index.
            pick = jax.lax.broadcasted_iota(
                jnp.int32, ks_ref.shape[1:], 1) == hk

            def head_scale(s_ref):
                return jnp.sum(jnp.where(pick, s_ref[0], 0.0), axis=1,
                               keepdims=True)              # (1, 1)

            kblk = kblk.astype(jnp.float32) * head_scale(ks_ref)
            vblk = vblk.astype(jnp.float32) * head_scale(vs_ref)
        attention_block_step(q_ref[0], kblk, vblk,
                             cols, qpos, kvlen, m_ref, l_ref, acc_ref,
                             scale=scale, causal=causal, soft_cap=soft_cap)

    @pl.when(ij == nb - 1)
    def _flush():
        o_ref[0] = attention_block_flush(l_ref, acc_ref, o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "soft_cap", "block_q", "interpret"),
)
def paged_attention(
    q: jax.Array,             # (B, Sq, H, D)   — model layout
    k_pages: jax.Array,       # (P, page_size, Hkv, D)
    v_pages: jax.Array,       # (P, page_size, Hkv, Dv)
    block_tables: jax.Array,  # (B, n_blocks) int32 physical page per block
    q_positions: Optional[jax.Array] = None,   # (B, Sq) int32; <0 → masked
    kv_valid_len: Optional[jax.Array] = None,  # (B,) int32; None → all keys
    *,
    kv_scales=None,           # int8 pools: ((P, Hkv), (P, Hkv)) fp32 scales
    causal: bool = True,
    scale: Optional[float] = None,
    soft_cap: Optional[float] = None,
    block_q: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Flash attention reading K/V through a block table.

    The key-block size IS the page size (``k_pages.shape[1]``): page
    granularity and kernel block granularity coincide by construction, the
    alignment the paper's block-streaming datapath assumes. Returns
    (B, Sq, H, Dv) in model layout.

    With int8 pools, ``kv_scales`` must carry the per-page-per-head fp32
    ``(k_scales, v_scales)`` arrays of shape (P, Hkv) (docs/quant.md
    #kv-pages); the kernel fetches each page's scale row alongside the
    page and dequantizes in-register, so the HBM stream stays int8.

    Compiled for a TPU, D and Dv must be multiples of 128 unless there is
    a single head (see the layout note above the index maps); other head
    dims raise ValueError.
    """
    B, Sq, H, D = q.shape
    P, ps, Hkv, Dv = v_pages.shape
    nb_early = block_tables.shape[1]
    pre = paged_preconditions(H, Hkv, k_pages.shape, v_pages.shape, nb_early,
                              interpret=interpret)
    # nb == 0 is legal here (short-circuited below); the other guards are
    # hard errors shared verbatim with the static contract.
    require(*pre[:-1])
    quantized = k_pages.dtype == jnp.int8
    if quantized != (v_pages.dtype == jnp.int8):
        raise ValueError(
            f"k_pages/v_pages dtype mismatch: {k_pages.dtype} vs "
            f"{v_pages.dtype}")
    if quantized:
        if kv_scales is None:
            raise ValueError(
                "int8 k_pages/v_pages need kv_scales=(k_scales, v_scales) "
                "per-page-per-head fp32 arrays of shape (P, Hkv)")
        k_scales, v_scales = kv_scales
        for name, s in (("k_scales", k_scales), ("v_scales", v_scales)):
            if tuple(s.shape) != (P, Hkv):
                raise ValueError(
                    f"{name} has shape {tuple(s.shape)}, expected "
                    f"(P, Hkv) = {(P, Hkv)}")
        k_scales = k_scales.astype(jnp.float32)
        v_scales = v_scales.astype(jnp.float32)
    elif kv_scales is not None:
        raise ValueError(
            f"kv_scales given but pages are {k_pages.dtype}, not int8")
    nb = nb_early
    if nb == 0:
        # Empty block table: no key block is visible (kv_valid_len is
        # clamped to nb * ps == 0 below), so every query row is fully
        # masked — the contract says exactly zeros. The grid (B, H, nq, 0)
        # would never run the flush step, so short-circuit here.
        return jnp.zeros((B, Sq, H, Dv), q.dtype)
    rep = H // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    bq = min(block_q, Sq)
    if q_positions is None:
        q_positions = jnp.broadcast_to(
            jnp.arange(Sq, dtype=jnp.int32), (B, Sq))
    q_positions = q_positions.astype(jnp.int32)
    if kv_valid_len is None:
        kv_valid_len = jnp.full((B,), nb * ps, jnp.int32)
    kv_valid_len = jnp.minimum(kv_valid_len.astype(jnp.int32), nb * ps)
    block_tables = block_tables.astype(jnp.int32)

    # pad Sq to a block multiple; padded query rows carry position -1
    # (fully masked → zero rows, sliced off below).
    pq = (-Sq) % bq
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, ((0, 0), (0, pq)),
                              constant_values=-1)
    Sq_p = Sq + pq
    nq = Sq_p // bq

    qpos_in = q_positions[..., None]        # (B, Sq_p, 1): (bq, 1) tiles

    kernel = functools.partial(_kernel, scale=scale, causal=causal,
                               soft_cap=soft_cap, bq=bq, ps=ps, nb=nb,
                               rep=rep, quantized=quantized)
    page_index_map = _make_page_index_map(rep)
    in_specs = [
        pl.BlockSpec((1, bq, 1), _pg_qpos_index_map),
        pl.BlockSpec((1, bq, D), _pg_q_index_map),
        # the paged indirection: the block table entry IS the index
        pl.BlockSpec((1, ps, D), page_index_map),
        pl.BlockSpec((1, ps, Dv), page_index_map),
    ]
    # heads folded into lanes (free reshapes; see the index-map note)
    operands = [block_tables, kv_valid_len, qpos_in,
                q.reshape(B, Sq_p, H * D), k_pages.reshape(P, ps, Hkv * D),
                v_pages.reshape(P, ps, Hkv * Dv)]
    if quantized:
        # each page's scales ride the same block-table indirection as the
        # page itself: one (1, Hkv) fp32 row per page.
        scale_spec = pl.BlockSpec((1, 1, Hkv), _page_scale_index_map)
        in_specs += [scale_spec, scale_spec]
        operands += [k_scales.reshape(P, 1, Hkv), v_scales.reshape(P, 1, Hkv)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,              # block_tables, kv_valid_len
        grid=(B, H, nq, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bq, Dv), _pg_o_index_map),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Sq_p, H * Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=ATTN_DIMENSION_SEMANTICS),
        interpret=interpret,
    )(*operands)
    return out.reshape(B, Sq_p, H, Dv)[:, :Sq]


def gather_pages(pages: jax.Array, block_tables: jax.Array,
                 max_len: Optional[int] = None) -> jax.Array:
    """Gather a (P, page_size, Hkv, D) pool back to dense (B, T, Hkv, D)
    caches through the block tables — the oracle/debug inverse of the paged
    layout (used by parity tests to feed mha_ref, never by the hot path)."""
    P, ps, Hkv, D = pages.shape
    B, nb = block_tables.shape
    dense = pages[block_tables.astype(jnp.int32)]       # (B, nb, ps, Hkv, D)
    dense = dense.reshape(B, nb * ps, Hkv, D)
    return dense if max_len is None else dense[:, :max_len]
