"""Tier-1 gate over the cross-backend differential harness (tests/parity.py).

Every (backend × dtype × shape) cell must hold: blockflow ≡ Pallas ≡ XLA ≡
reference, exactly for int8, within per-dtype tolerances for fp — plus the
quantized W8A8 route across all backends. New backends registered in
core/api.py extend parity.BACKENDS and inherit this gate.
"""
import pytest

import parity


@pytest.mark.parametrize("backend", parity.BACKENDS)
@pytest.mark.parametrize("dtype", parity.DTYPES)
@pytest.mark.parametrize("shape", parity.SHAPES,
                         ids=lambda s: "x".join(map(str, s)))
def test_backend_dtype_parity(backend, dtype, shape):
    parity.check_cell(backend, dtype, shape)


@pytest.mark.parametrize("backend", parity.BACKENDS)
@pytest.mark.parametrize("shape", parity.SHAPES[:3],
                         ids=lambda s: "x".join(map(str, s)))
def test_quantized_route_parity(backend, shape):
    parity.check_quantized_cell(backend, shape)


@pytest.mark.parametrize("backend", parity.ATTN_BACKENDS)
@pytest.mark.parametrize("dtype", parity.ATTN_DTYPES)
@pytest.mark.parametrize("case", parity.ATTN_CASES, ids=lambda c: c.name)
def test_attention_backend_parity(backend, dtype, case):
    """Every attention backend (fused flash kernel in interpret mode, the
    unfused host-softmax baseline) must match kernels/ref.py::mha_ref on
    prefill, decode-with-offsets, GQA, ragged non-causal keys, and masked
    serving rows — the AttentionPolicy contract (docs/attention.md)."""
    parity.check_attention_cell(backend, dtype, case)


@pytest.mark.parametrize("case", parity.ATTN_CASES, ids=lambda c: c.name)
def test_attention_quantized_kv_parity(case):
    """The quantized-KV paged cells (AttentionPolicy(kv_dtype="int8")):
    int8 pages + per-page-per-head scales, dequantized inside the kernel's
    K/V fetch, vs mha_ref on the dequantized pool (docs/quant.md#kv-pages).
    Same case set as the fp grid — offsets, GQA, masked rows included."""
    parity.check_quantized_attention_cell("paged", case)


def test_attention_fused_vs_unfused_direct():
    """The backends must also agree with *each other* (not just each
    within tolerance of the oracle) on the decode case — the cell serving
    exercises every step. The paged backend reads the same K/V through a
    shuffled block table; with page_size == block_k its blocking (and
    hence accumulation order) is identical to fused, so those two must
    agree *bitwise*."""
    import numpy as np
    case = parity.ATTN_CASES[2]          # decode_long_cache
    q, k, v, qp, kl = parity.make_attention_operands(case, "float32")
    from repro.core import api
    from repro.core.plan import AttentionPolicy
    ps = parity.ATTN_PAGE_SIZE
    kp, vp, bt = parity.make_paged_operands(k, v, page_size=ps)
    outs = {}
    for b in parity.ATTN_BACKENDS:
        pol = AttentionPolicy(backend=b, block_q=32, block_k=ps,
                              page_size=ps)
        kw = (dict(block_tables=bt) if b.startswith("paged") else {})
        operands = (q, kp, vp) if b.startswith("paged") else (q, k, v)
        outs[b] = np.asarray(api.attention(
            *operands, q_positions=qp, kv_valid_len=kl, causal=case.causal,
            policy=pol, **kw))
    np.testing.assert_allclose(outs["unfused"], outs["fused"],
                               atol=3e-5, rtol=3e-5)
    np.testing.assert_array_equal(outs["paged"], outs["fused"])


def test_dense_backends_reject_block_tables():
    """Handing a paged pool + block table to a dense backend must fail
    loudly (it would silently misread the pool layout otherwise)."""
    import jax.numpy as jnp
    import pytest
    from repro.core import api
    from repro.core.plan import AttentionPolicy
    q = jnp.zeros((1, 1, 2, 8))
    kp = jnp.zeros((4, 16, 1, 8))
    bt = jnp.zeros((1, 2), jnp.int32)
    for b in ("unfused", "fused"):
        with pytest.raises(ValueError, match="paged"):
            api.attention(q, kp, kp, q_positions=jnp.zeros((1, 1), jnp.int32),
                          kv_valid_len=jnp.ones((1,), jnp.int32),
                          block_tables=bt,
                          policy=AttentionPolicy(backend=b))


def test_attention_grid_runner_smoke():
    """The CLI sweep CI uses must run the attention grid end-to-end."""
    import io
    results = parity.run_attention_grid(backends=("unfused",),
                                        dtypes=("float32",),
                                        cases=parity.ATTN_CASES[:1],
                                        out=io.StringIO())
    assert all(r.ok for r in results)


def test_int8_blockflow_exactly_matches_reference():
    """Acceptance: int8 blockflow-vs-reference exact integer equality on a
    larger-than-one-block problem (multi K-blocks exercise accumulation)."""
    r = parity.check_cell("blockflow", "int8", (130, 24, 56))
    assert r.detail == "exact"


def test_grid_runner_smoke():
    """The CLI entry CI uses must sweep a small grid end-to-end."""
    import io
    results = parity.run_grid(backends=("xla",), dtypes=("int8",),
                              shapes=((8, 8, 8),), out=io.StringIO())
    assert all(r.ok for r in results)
