"""Cross-backend differential harness (GEMM and attention grids).

Every registered GEMM backend must be provably equivalent on every dtype the
paper's MAC units cover (Table 2): the blockflow oracle (faithful Algorithm
1), the Pallas kernel (interpret mode on CPU), and XLA einsum must agree
with the pure-jnp reference within per-dtype tolerances — and *exactly* (in
integers) for int8, where accumulation in int32 is associative.

The grid also sweeps the quantized W8A8 route (``GemmPolicy(weight_dtype=
"int8")``): all backends share the same quantization functions and the same
rank-1 dequant, so their fp32 outputs must agree bitwise-tight with the
unfused reference formula.

The **attention grid** applies the same discipline to the AttentionPolicy
registry (docs/attention.md): every attention backend — the offset-aware
fused flash kernel (interpret mode on CPU), the unfused einsum +
host-softmax baseline, and the block-table **paged** kernel
(kernels/paged_attention.py, docs/serving.md) — must match
``kernels/ref.py::mha_ref`` on cases covering prefill, single-token decode
against a long ragged cache, GQA head grouping, non-causal ragged keys,
and serving's masked position −1 rows. Paged cells scatter the dense K/V
into a page pool under a *shuffled* page assignment with garbage-filled
distractor pages, so any fetch outside the block table, any masking slip
past ``kv_valid_len``, or any logical/physical confusion diverges loudly.

The **sharded grid** extends both disciplines across device meshes: every
GEMM cell re-runs column- and row-parallel through the shard_map'd TP layer
(repro/distributed/tp.py) and every attention cell re-runs with heads (and
KV pools) sharded over the model axis, on meshes of shape
(1,1)/(2,1)/(1,2)/(2,2) — asserting sharded ≡ unsharded to the same
per-dtype tolerances. These cells need a multi-device host
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``): CI's
``parity-sharded`` job sets it, and the tier-1 gate runs them through the
subprocess-isolated ``tests/test_parity_sharded.py`` (conftest.py must stay
1-device per its own warning).

Used three ways:
  * ``tests/test_parity.py`` parametrizes pytest over the grids (tier-1
    gate); ``tests/test_parity_sharded.py`` adds the mesh axis via a
    subprocess;
  * CI's dtype-matrix job runs ``python tests/parity.py --dtypes <dt>``
    (GEMM cells for every dtype, attention cells for the fp dtypes;
    ``int8`` additionally selects the quantized-KV paged cells —
    ``AttentionPolicy(kv_dtype="int8")``, oracle on the dequantized
    pool); the
    ``parity-sharded`` job runs ``--sharded --dtypes <dt>`` on a forced
    4-device host;
  * new backends/dtypes/cases/mesh shapes extend BACKENDS / DTYPES /
    SHAPES / ATTN_BACKENDS / ATTN_CASES / MESH_SHAPES and inherit the
    whole gate.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
from typing import Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core import api
from repro.core import quant as Q
from repro.core.plan import AttentionPolicy, GemmPolicy
from repro.kernels.ref import mha_ref

BACKENDS = ("xla", "blockflow", "pallas")
DTYPES = ("float32", "bfloat16", "int8")

# (M, K, N): MXU-aligned, multi-block, ragged/odd (padding paths), and the
# decode-like skinny-M GEMV.
SHAPES = (
    (8, 8, 8),
    (64, 96, 48),
    (33, 17, 65),
    (1, 64, 128),
    (130, 24, 56),
)

# (atol, rtol) per dtype; int8 demands exact integer equality.
TOLS = {
    "float32": (1e-4, 1e-5),
    "bfloat16": (5e-2, 5e-2),
    "int8": (0.0, 0.0),
}


@dataclasses.dataclass
class ParityResult:
    backend: str
    dtype: str
    shape: Tuple[int, int, int]
    max_err: float
    ok: bool
    detail: str = ""


def make_operands(dtype: str, M: int, K: int, N: int, seed: int = 0):
    """Deterministic operands per (dtype, shape) cell."""
    rng = np.random.default_rng((seed * 7919 + M * 1000003 + K * 1009 + N)
                                % 2**32)
    if dtype == "int8":
        a = rng.integers(-127, 128, (M, K)).astype(np.int8)
        b = rng.integers(-127, 128, (K, N)).astype(np.int8)
        return jnp.asarray(a), jnp.asarray(b)
    a = rng.standard_normal((M, K)).astype(np.float32)
    b = rng.standard_normal((K, N)).astype(np.float32)
    return (jnp.asarray(a).astype(jnp.dtype(dtype)),
            jnp.asarray(b).astype(jnp.dtype(dtype)))


def reference(a, b) -> np.ndarray:
    """Ground truth: int64 exact for integer inputs, fp32 accumulation else."""
    if jnp.issubdtype(a.dtype, jnp.integer):
        return np.asarray(a, np.int64) @ np.asarray(b, np.int64)
    return (np.asarray(a, np.float32) @ np.asarray(b, np.float32))


def check_cell(backend: str, dtype: str,
               shape: Tuple[int, int, int]) -> ParityResult:
    """One grid cell: backend output vs reference. Raises AssertionError
    with full context on disagreement; returns the passing ParityResult."""
    M, K, N = shape
    a, b = make_operands(dtype, M, K, N)
    ref = reference(a, b)
    out = api.matmul(a, b, policy=GemmPolicy(backend=backend))
    assert out.shape == (M, N), (out.shape, shape)
    ctx = f"backend={backend} dtype={dtype} shape={shape}"
    if dtype == "int8":
        assert out.dtype == jnp.int32, f"{ctx}: got {out.dtype}, want int32"
        got = np.asarray(out, np.int64)
        np.testing.assert_array_equal(
            got, ref, err_msg=f"{ctx}: int8 GEMM must be integer-exact")
        return ParityResult(backend, dtype, shape, 0.0, True, "exact")
    atol, rtol = TOLS[dtype]
    got = np.asarray(out, np.float32)
    err = float(np.abs(got - ref).max()) if got.size else 0.0
    np.testing.assert_allclose(got, ref, atol=atol, rtol=rtol, err_msg=ctx)
    return ParityResult(backend, dtype, shape, err, True)


def check_quantized_cell(backend: str,
                         shape: Tuple[int, int, int]) -> ParityResult:
    """The W8A8 route under GemmPolicy(weight_dtype="int8") vs the unfused
    dequant reference — same int8 operands, same scales, so every backend
    must land within fp32 noise of the rank-1 rescaled int32 GEMM."""
    M, K, N = shape
    a, w = make_operands("float32", M, K, N, seed=1)
    aq, sa = Q.quantize_activations(a)
    wq, sw = Q.quantize_weight(w)
    c_int = np.asarray(aq, np.int64) @ np.asarray(wq, np.int64)
    ref = np.asarray(Q.dequantize_gemm(jnp.asarray(c_int, jnp.int32),
                                       sa, sw), np.float32)
    pol = GemmPolicy(backend=backend, weight_dtype="int8")
    out = np.asarray(api.linear(a, w, policy=pol), np.float32)
    ctx = f"quantized backend={backend} shape={shape}"
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-6, err_msg=ctx)
    # and the quantization error vs the fp product stays bounded:
    fp = reference(a, w)
    budget = np.abs(fp).max() * 0.05 + 1e-3
    err = float(np.abs(out - fp).max())
    assert err <= budget, f"{ctx}: quant error {err} > budget {budget}"
    return ParityResult(backend, "int8(w8a8)", shape, err, True)


# ---------------------------------------------------------------------------
# Attention grid (backend × dtype × case)
# ---------------------------------------------------------------------------

ATTN_BACKENDS = ("unfused", "fused", "paged")
ATTN_DTYPES = ("float32", "bfloat16")       # fp only: scores are fp32 always
ATTN_PAGE_SIZE = 16                          # key-block of the paged cells

# (atol, rtol) per dtype for attention outputs (post-softmax, O(1) scale).
ATTN_TOLS = {"float32": (3e-5, 3e-5), "bfloat16": (3e-2, 3e-2)}


@dataclasses.dataclass(frozen=True)
class AttnCase:
    """One attention-grid cell: shapes plus the offset/length semantics.

    q_offsets: per-batch-row position of the first query (−1 → the whole
    row is masked, serving's position −1 contract); None → the default
    bottom-right alignment. kv_lens: per-row valid key count; None → T.
    """

    name: str
    B: int
    Sq: int
    T: int
    H: int
    Hkv: int
    causal: bool = True
    q_offsets: Optional[Tuple[int, ...]] = None
    kv_lens: Optional[Tuple[int, ...]] = None


ATTN_CASES = (
    # pure prefill, MHA, block-aligned
    AttnCase("prefill_mha", B=2, Sq=32, T=32, H=4, Hkv=4),
    # prefill with GQA grouping and a ragged (non-block-multiple) length
    AttnCase("prefill_gqa_ragged", B=2, Sq=33, T=33, H=4, Hkv=2),
    # single-token decode against a long, partially filled cache (per-row
    # offsets — the continuous-batching slots)
    AttnCase("decode_long_cache", B=3, Sq=1, T=96, H=4, Hkv=2,
             q_offsets=(5, 80, 37), kv_lens=(6, 81, 38)),
    # decode batch containing masked (position −1) serving rows
    AttnCase("decode_masked_rows", B=3, Sq=1, T=64, H=2, Hkv=1,
             q_offsets=(12, -1, 3), kv_lens=(13, 0, 4)),
    # chunked prefill: a short query block continuing a long cache
    AttnCase("prefill_chunk_offset", B=2, Sq=8, T=64, H=2, Hkv=2,
             q_offsets=(24, 40), kv_lens=(32, 48)),
    # non-causal ragged keys (the old kernel raised ValueError here)
    AttnCase("noncausal_ragged", B=2, Sq=17, T=45, H=2, Hkv=1, causal=False,
             kv_lens=(45, 29)),
)


def make_attention_operands(case: AttnCase, dtype: str, seed: int = 0):
    """Deterministic (q, k, v, q_positions, kv_valid_len) per cell."""
    rng = np.random.default_rng(
        (seed * 7919 + case.B * 1000003 + case.Sq * 1009 + case.T) % 2**32)
    dt = jnp.dtype(dtype)
    q = jnp.asarray(rng.standard_normal(
        (case.B, case.Sq, case.H, 16), np.float32)).astype(dt)
    k = jnp.asarray(rng.standard_normal(
        (case.B, case.T, case.Hkv, 16), np.float32)).astype(dt)
    v = jnp.asarray(rng.standard_normal(
        (case.B, case.T, case.Hkv, 16), np.float32)).astype(dt)
    if case.q_offsets is None:
        q_positions = jnp.broadcast_to(
            jnp.arange(case.Sq, dtype=jnp.int32) + (case.T - case.Sq),
            (case.B, case.Sq))
    else:
        offs = np.asarray(case.q_offsets, np.int32)[:, None]
        q_positions = jnp.asarray(
            np.where(offs < 0, -1, offs + np.arange(case.Sq)[None, :])
            .astype(np.int32))
    kv_valid_len = jnp.asarray(
        np.full((case.B,), case.T, np.int32) if case.kv_lens is None
        else np.asarray(case.kv_lens, np.int32))
    return q, k, v, q_positions, kv_valid_len


def make_paged_operands(k, v, page_size: int = ATTN_PAGE_SIZE,
                        seed: int = 0, n_distractors: int = 3,
                        garbage: float = 100.0):
    """Scatter dense (B, T, Hkv, D) K/V into page pools under a shuffled
    page assignment. Returns (k_pages, v_pages, block_tables); distractor
    pages and every unwritten slot are filled with large garbage so an
    out-of-table fetch cannot silently agree with the oracle. (Also the
    single pool-construction helper for tests/test_paged_attention.py.)"""
    B, T, Hkv, D = k.shape
    nb = -(-T // page_size)
    P = B * nb + n_distractors                  # garbage distractor pages
    rng = np.random.default_rng(seed * 31 + B * 101 + T)
    kp = (rng.standard_normal((P, page_size, Hkv, D)) * garbage).astype(
        np.float32)
    vp = (rng.standard_normal((P, page_size, Hkv, D)) * garbage).astype(
        np.float32)
    assign = rng.permutation(P)[:B * nb].reshape(B, nb)
    kn, vn = np.asarray(k, np.float32), np.asarray(v, np.float32)
    for b in range(B):
        for t in range(T):
            page = assign[b, t // page_size]
            kp[page, t % page_size] = kn[b, t]
            vp[page, t % page_size] = vn[b, t]
    dt = jnp.dtype(k.dtype)
    return (jnp.asarray(kp).astype(dt), jnp.asarray(vp).astype(dt),
            jnp.asarray(assign.astype(np.int32)))


def check_attention_cell(backend: str, dtype: str,
                         case: AttnCase) -> ParityResult:
    """One attention cell: backend output vs the mha_ref oracle, plus the
    masked-row zero contract. Raises AssertionError with context. Paged
    backends read K/V through a shuffled block table over a distractor-
    laden pool; the oracle still sees the dense cache."""
    q, k, v, q_positions, kv_valid_len = make_attention_operands(case, dtype)
    ref = np.asarray(mha_ref(q, k, v, causal=case.causal,
                             q_positions=q_positions,
                             kv_valid_len=kv_valid_len), np.float32)
    pol = AttentionPolicy(backend=backend, block_q=32, block_k=32,
                          page_size=ATTN_PAGE_SIZE)
    if backend.startswith("paged"):
        kp, vp, bt = make_paged_operands(k, v)
        out = api.attention(q, kp, vp, q_positions=q_positions,
                            kv_valid_len=kv_valid_len, causal=case.causal,
                            block_tables=bt, policy=pol)
    else:
        out = api.attention(q, k, v, q_positions=q_positions,
                            kv_valid_len=kv_valid_len, causal=case.causal,
                            policy=pol)
    ctx = f"attention backend={backend} dtype={dtype} case={case.name}"
    assert out.shape == q.shape[:3] + (v.shape[-1],), (ctx, out.shape)
    got = np.asarray(out, np.float32)
    atol, rtol = ATTN_TOLS[dtype]
    np.testing.assert_allclose(got, ref, atol=atol, rtol=rtol, err_msg=ctx)
    masked = np.asarray(q_positions)[:, 0] < 0
    if masked.any():
        assert np.abs(got[masked]).max() == 0.0, \
            f"{ctx}: masked rows must be exactly zero"
    err = float(np.abs(got - ref).max()) if got.size else 0.0
    return ParityResult(backend, dtype, (case.B, case.Sq, case.T), err, True,
                        case.name)


def check_quantized_attention_cell(backend: str,
                                   case: AttnCase) -> ParityResult:
    """One quantized-KV cell (AttentionPolicy(kv_dtype="int8")): the paged
    backend reads int8 pages + (P, Hkv) per-page-per-head scales and
    dequantizes inside the key/value fetch. The oracle is mha_ref on the
    DEQUANTIZED pool — the in-kernel dequant is what is under test here,
    not the quantization error (core/quant.py owns that bound) — so the
    fp32 attention tolerances apply unchanged."""
    from repro.kernels.paged_attention import gather_pages

    q, k, v, q_positions, kv_valid_len = make_attention_operands(
        case, "float32")
    kp, vp, bt = make_paged_operands(k, v)
    qk, ks = Q.quantize_kv_pages(kp)
    qv, vs = Q.quantize_kv_pages(vp)
    ref = np.asarray(mha_ref(
        q, gather_pages(Q.dequantize_kv_pages(qk, ks), bt, case.T),
        gather_pages(Q.dequantize_kv_pages(qv, vs), bt, case.T),
        causal=case.causal, q_positions=q_positions,
        kv_valid_len=kv_valid_len), np.float32)
    pol = AttentionPolicy(backend=backend, block_q=32, block_k=32,
                          page_size=ATTN_PAGE_SIZE, kv_dtype="int8")
    out = api.attention(q, qk, qv, q_positions=q_positions,
                        kv_valid_len=kv_valid_len, causal=case.causal,
                        block_tables=bt, kv_scales=(ks, vs), policy=pol)
    ctx = f"attention backend={backend} kv_dtype=int8 case={case.name}"
    got = np.asarray(out, np.float32)
    atol, rtol = ATTN_TOLS["float32"]
    np.testing.assert_allclose(got, ref, atol=atol, rtol=rtol, err_msg=ctx)
    masked = np.asarray(q_positions)[:, 0] < 0
    if masked.any():
        assert np.abs(got[masked]).max() == 0.0, \
            f"{ctx}: masked rows must be exactly zero"
    err = float(np.abs(got - ref).max()) if got.size else 0.0
    return ParityResult(backend, "int8(kv)", (case.B, case.Sq, case.T),
                        err, True, case.name)


def run_attention_grid(backends: Sequence[str] = ATTN_BACKENDS,
                       dtypes: Sequence[str] = ATTN_DTYPES,
                       cases: Sequence[AttnCase] = ATTN_CASES,
                       out=sys.stdout) -> list:
    """Sweep the attention grid; raises on first divergence. "int8" in
    ``dtypes`` selects the quantized-KV cells (paged backends only — the
    policy layer rejects kv_dtype elsewhere), not an int8 compute dtype."""
    results = []
    for dtype in dtypes:
        if dtype == "int8":
            for backend in backends:
                if not backend.startswith("paged"):
                    continue            # kv_dtype is a paged-only policy
                for case in cases:
                    r = check_quantized_attention_cell(backend, case)
                    results.append(r)
                    print(f"parity {backend:17s} int8(kv)  "
                          f"attn:{case.name:22s} max_err={r.max_err:.2e}",
                          file=out)
            continue
        if dtype not in ATTN_TOLS:
            continue                    # integer dtypes: GEMM-only
        for backend in backends:
            for case in cases:
                r = check_attention_cell(backend, dtype, case)
                results.append(r)
                print(f"parity {backend:17s} {dtype:9s} "
                      f"attn:{case.name:22s} max_err={r.max_err:.2e}",
                      file=out)
    return results


# ---------------------------------------------------------------------------
# Sharded grid (mesh × backend × dtype): shard_map'd TP ≡ unsharded
# ---------------------------------------------------------------------------

# (data, model) mesh shapes; (2,2) needs the forced 4-device host.
MESH_SHAPES = ((1, 1), (2, 1), (1, 2), (2, 2))

SHARDED_GEMM_BACKENDS = ("xla", "blockflow")
SHARDED_ATTN_BACKENDS = ("fused", "paged")


def make_tp_mesh(shape: Tuple[int, int]):
    """A (data, model) mesh over the first shape[0]*shape[1] local devices."""
    import jax
    from jax.sharding import Mesh
    need = shape[0] * shape[1]
    devs = jax.devices()
    if len(devs) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, host has {len(devs)}; "
            f"set XLA_FLAGS=--xla_force_host_platform_device_count=4 "
            f"before jax initializes (tests/test_parity_sharded.py does)")
    return Mesh(np.asarray(devs[:need]).reshape(shape), ("data", "model"))


def check_sharded_gemm_cell(mesh_shape: Tuple[int, int], backend: str,
                            dtype: str,
                            shape: Tuple[int, int, int]) -> ParityResult:
    """One sharded GEMM cell: the TP layer's column-parallel AND
    row-parallel (psum) paths vs the unsharded backend, exact for int8,
    per-dtype tolerances else. Non-divisible shapes exercise the
    replicated fallback (trivially equal — still asserted)."""
    from repro.distributed import tp
    M, K, N = shape
    a, b = make_operands(dtype, M, K, N)
    pol = GemmPolicy(backend=backend)
    ref = np.asarray(api.matmul(a, b, policy=pol))
    ctx = tp.make_context(make_tp_mesh(mesh_shape))
    ctx_desc = f"mesh={mesh_shape}"
    bias = (None if dtype == "int8"
            else jnp.asarray(np.arange(N, dtype=np.float32) * 0.25,
                             b.dtype))
    with tp.use_tp(ctx):
        # "mlp" → model axis: second position = column-parallel (N split),
        # first position = row-parallel (K split, fp32/int32 psum).
        col = np.asarray(tp.matmul(a, b, axes=("embed", "mlp"), policy=pol))
        row = np.asarray(tp.matmul(a, b, axes=("mlp", "embed"), policy=pol))
        colb = (None if bias is None else np.asarray(
            tp.linear(a, b, bias, axes=("embed", "mlp"), policy=pol)))
    checks = [("column", col, ref), ("row", row, ref)]
    if bias is not None:
        # sharded-bias path: the (N,) bias splits with its output columns
        refb = np.asarray(api.linear(a, b, bias, policy=pol))
        checks.append(("column+bias", colb, refb))
    err = 0.0
    for name, got, ref in checks:
        cx = (f"sharded {ctx_desc} {name}-parallel backend={backend} "
              f"dtype={dtype} shape={shape}")
        if dtype == "int8":
            np.testing.assert_array_equal(got, ref, err_msg=cx)
        else:
            atol, rtol = TOLS[dtype]
            np.testing.assert_allclose(got, ref, atol=atol, rtol=rtol,
                                       err_msg=cx)
            err = max(err, float(np.abs(got.astype(np.float32)
                                        - ref.astype(np.float32)).max()))
    return ParityResult(backend, dtype, shape, err, True,
                        f"mesh{mesh_shape[0]}x{mesh_shape[1]}")


def check_sharded_attention_cell(mesh_shape: Tuple[int, int], backend: str,
                                 dtype: str, case: AttnCase) -> ParityResult:
    """One sharded attention cell: heads (and the paged pool's KV heads)
    sharded over the model axis through tp.attention vs the unsharded
    backend and the mha_ref oracle. MQA cases (Hkv=1) exercise the
    KV-replication fallback; the masked-row zero contract must survive
    sharding."""
    from repro.distributed import tp
    q, k, v, q_positions, kv_valid_len = make_attention_operands(case, dtype)
    pol = AttentionPolicy(backend=backend, block_q=32, block_k=32,
                          page_size=ATTN_PAGE_SIZE)
    ref = np.asarray(mha_ref(q, k, v, causal=case.causal,
                             q_positions=q_positions,
                             kv_valid_len=kv_valid_len), np.float32)
    if backend.startswith("paged"):
        kop, vop, bt = make_paged_operands(k, v)
    else:
        kop, vop, bt = k, v, None
    unsharded = np.asarray(api.attention(
        q, kop, vop, q_positions=q_positions, kv_valid_len=kv_valid_len,
        causal=case.causal, block_tables=bt, policy=pol), np.float32)
    ctx = tp.make_context(make_tp_mesh(mesh_shape))
    with tp.use_tp(ctx):
        out = tp.attention(q, kop, vop, q_positions=q_positions,
                           kv_valid_len=kv_valid_len, causal=case.causal,
                           block_tables=bt, policy=pol)
    got = np.asarray(out, np.float32)
    cx = (f"sharded mesh={mesh_shape} attention backend={backend} "
          f"dtype={dtype} case={case.name}")
    atol, rtol = ATTN_TOLS[dtype]
    np.testing.assert_allclose(got, unsharded, atol=atol, rtol=rtol,
                               err_msg=f"{cx}: sharded vs unsharded")
    np.testing.assert_allclose(got, ref, atol=atol, rtol=rtol,
                               err_msg=f"{cx}: sharded vs oracle")
    masked = np.asarray(q_positions)[:, 0] < 0
    if masked.any():
        assert np.abs(got[masked]).max() == 0.0, \
            f"{cx}: masked rows must stay exactly zero under sharding"
    err = float(np.abs(got - ref).max()) if got.size else 0.0
    return ParityResult(backend, dtype, (case.B, case.Sq, case.T), err, True,
                        f"{case.name}@mesh{mesh_shape[0]}x{mesh_shape[1]}")


def run_sharded_grid(mesh_shapes: Sequence[Tuple[int, int]] = MESH_SHAPES,
                     dtypes: Sequence[str] = DTYPES,
                     gemm_backends: Sequence[str] = SHARDED_GEMM_BACKENDS,
                     attn_backends: Sequence[str] = SHARDED_ATTN_BACKENDS,
                     shapes: Sequence[Tuple[int, int, int]] = SHAPES,
                     cases: Sequence[AttnCase] = ATTN_CASES,
                     out=sys.stdout) -> list:
    """Sweep the sharded grids; raises on first divergence."""
    results = []
    for ms in mesh_shapes:
        for dtype in dtypes:
            for backend in gemm_backends:
                for shape in shapes:
                    r = check_sharded_gemm_cell(ms, backend, dtype, shape)
                    results.append(r)
                    print(f"parity {backend:17s} {dtype:9s} "
                          f"{'x'.join(map(str, shape)):12s} "
                          f"max_err={r.max_err:.2e} {r.detail}", file=out)
            if dtype not in ATTN_TOLS:
                continue                # integer dtypes: GEMM-only
            for backend in attn_backends:
                for case in cases:
                    r = check_sharded_attention_cell(ms, backend, dtype,
                                                     case)
                    results.append(r)
                    print(f"parity {backend:17s} {dtype:9s} "
                          f"attn:{r.detail:34s} max_err={r.max_err:.2e}",
                          file=out)
    return results


def run_grid(backends: Sequence[str] = BACKENDS,
             dtypes: Sequence[str] = DTYPES,
             shapes: Sequence[Tuple[int, int, int]] = SHAPES,
             *, quantized: bool = True,
             out=sys.stdout) -> list:
    """Sweep the full grid; returns results, raising on first failure."""
    results = []
    for dtype in dtypes:
        for backend in backends:
            for shape in shapes:
                r = check_cell(backend, dtype, shape)
                results.append(r)
                print(f"parity {backend:17s} {dtype:9s} "
                      f"{'x'.join(map(str, shape)):12s} "
                      f"max_err={r.max_err:.2e} {r.detail}", file=out)
    if quantized and "int8" in dtypes:
        for backend in backends:
            for shape in shapes[:3]:
                r = check_quantized_cell(backend, shape)
                results.append(r)
                print(f"parity {backend:17s} w8a8      "
                      f"{'x'.join(map(str, shape)):12s} "
                      f"max_err={r.max_err:.2e}", file=out)
    return results


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dtypes", nargs="+", default=list(DTYPES),
                    choices=list(DTYPES))
    ap.add_argument("--backends", nargs="+", default=list(BACKENDS))
    ap.add_argument("--no-quantized", action="store_true",
                    help="skip the W8A8 weight_dtype route cells")
    ap.add_argument("--no-attention", action="store_true",
                    help="skip the attention backend grid (runs for the fp "
                         "dtypes in --dtypes)")
    ap.add_argument("--attn-backends", nargs="+",
                    default=list(ATTN_BACKENDS),
                    help="attention grid backends; paged cells "
                         "read K/V through shuffled block tables over a "
                         "distractor-laden page pool")
    ap.add_argument("--sharded", action="store_true",
                    help="run the SHARDED grids instead (mesh × backend × "
                         "dtype): shard_map'd TP GEMM (column+row) and "
                         "head-sharded attention vs unsharded, over "
                         "(1,1)/(2,1)/(1,2)/(2,2) meshes. Needs "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=4 set before jax initializes")
    ap.add_argument("--mesh-shapes", nargs="+", default=None,
                    help="sharded grid mesh shapes as DxM (e.g. 2x2)")
    args = ap.parse_args(argv)
    if args.sharded:
        shapes = (tuple(tuple(int(x) for x in m.split("x"))
                        for m in args.mesh_shapes)
                  if args.mesh_shapes else MESH_SHAPES)
        results = run_sharded_grid(mesh_shapes=shapes, dtypes=args.dtypes)
        print(f"parity[sharded]: {len(results)} cells OK "
              f"(meshes={list(shapes)}, dtypes={args.dtypes})")
        return 0
    results = run_grid(args.backends, args.dtypes,
                       quantized=not args.no_quantized)
    if not args.no_attention:
        results += run_attention_grid(
            backends=args.attn_backends,
            dtypes=[d for d in args.dtypes
                    if d in ATTN_TOLS or d == "int8"])
    print(f"parity: {len(results)} cells OK "
          f"(backends={args.backends}, dtypes={args.dtypes})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
