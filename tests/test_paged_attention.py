"""Paged attention kernel: block-table indirection vs the mha_ref oracle.

The parity grid (tests/parity.py) covers the backend-level contract; these
tests hit kernels/paged_attention.py directly for the properties only the
paged layout can break: shuffled physical assignment, garbage distractor
pages, unallocated-tail block-table entries, partial last pages, and the
page-gather inverse.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from parity import make_paged_operands

from repro.kernels.paged_attention import gather_pages, paged_attention
from repro.kernels.ref import mha_ref


def build_paged(rng, B, T, Hkv, D, ps, garbage=100.0):
    """Dense K/V plus an equivalent shuffled, distractor-laden pool —
    pool construction shared with the parity harness (one layout helper,
    tests/parity.py::make_paged_operands)."""
    k = jnp.asarray(rng.standard_normal((B, T, Hkv, D)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((B, T, Hkv, D)).astype(np.float32))
    kp, vp, bt = make_paged_operands(k, v, page_size=ps,
                                     seed=int(rng.integers(1 << 16)),
                                     garbage=garbage)
    return k, v, kp, vp, bt


@pytest.mark.parametrize("case", [
    # name, B, Sq, T, H, Hkv, ps, causal, q_offsets, kv_lens
    ("prefill", 2, 32, 32, 4, 4, 8, True, None, None),
    ("prefill_gqa_ragged", 2, 33, 33, 4, 2, 8, True, None, None),
    ("decode_offsets", 3, 1, 96, 4, 2, 16, True, (5, 80, 37), (6, 81, 38)),
    ("decode_masked_row", 3, 1, 64, 2, 1, 16, True, (12, -1, 3), (13, 0, 4)),
    ("chunked_prefill", 2, 8, 64, 2, 2, 16, True, (24, 40), (32, 48)),
    ("noncausal_ragged", 2, 17, 45, 2, 1, 16, False, None, (45, 29)),
], ids=lambda c: c[0])
def test_paged_kernel_matches_ref(case):
    name, B, Sq, T, H, Hkv, ps, causal, q_off, kv_lens = case
    D = 16
    rng = np.random.default_rng(hash(name) % 2**32)
    k, v, kp, vp, bt = build_paged(rng, B, T, Hkv, D, ps)
    q = jnp.asarray(rng.standard_normal((B, Sq, H, D)).astype(np.float32))
    if q_off is None:
        qpos = jnp.broadcast_to(
            jnp.arange(Sq, dtype=jnp.int32) + (T - Sq), (B, Sq))
    else:
        offs = np.asarray(q_off, np.int32)[:, None]
        qpos = jnp.asarray(np.where(
            offs < 0, -1, offs + np.arange(Sq)[None]).astype(np.int32))
    kvl = jnp.asarray(np.asarray(kv_lens, np.int32) if kv_lens is not None
                      else np.full((B,), T, np.int32))
    out = paged_attention(q, kp, vp, bt, qpos, kvl, causal=causal,
                          block_q=8, interpret=True)
    ref = mha_ref(q, k, v, causal=causal, q_positions=qpos,
                  kv_valid_len=kvl)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=3e-5, rtol=3e-5)
    masked = np.asarray(qpos)[:, 0] < 0
    if masked.any():
        assert np.abs(np.asarray(out, np.float32)[masked]).max() == 0.0


def test_unallocated_tail_entries_are_dead():
    """Block-table entries past kv_valid_len may point anywhere valid (the
    engine leaves them at 0): they must contribute nothing."""
    rng = np.random.default_rng(7)
    B, T, H, D, ps = 2, 24, 2, 8, 8
    k, v, kp, vp, bt = build_paged(rng, B, T, H, D, ps)
    q = jnp.asarray(rng.standard_normal((B, 1, H, D)).astype(np.float32))
    qpos = jnp.asarray([[4], [9]], jnp.int32)
    kvl = jnp.asarray([5, 10], jnp.int32)
    base = paged_attention(q, kp, vp, bt, qpos, kvl, block_q=8,
                           interpret=True)
    # rewrite every tail entry (blocks past the valid prefix) to page 0
    bt_n = np.asarray(bt).copy()
    for b in range(B):
        first_dead = -(-int(np.asarray(kvl)[b]) // ps)
        bt_n[b, first_dead:] = 0
    redirected = paged_attention(q, kp, vp, jnp.asarray(bt_n), qpos, kvl,
                                 block_q=8, interpret=True)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(redirected))


def test_gather_pages_inverts_layout():
    rng = np.random.default_rng(3)
    B, T, H, D, ps = 3, 40, 2, 8, 8
    k, v, kp, vp, bt = build_paged(rng, B, T, H, D, ps)
    np.testing.assert_array_equal(
        np.asarray(gather_pages(kp, bt, T)), np.asarray(k))
    np.testing.assert_array_equal(
        np.asarray(gather_pages(vp, bt, T)), np.asarray(v))


def test_empty_block_table_returns_zeros():
    """Regression: n_blocks == 0 (a zero-token probe) used to build a
    grid=(B, H, nq, 0) whose flush step never ran, returning uninitialized
    output. With no key block visible, the masked-row contract demands
    exactly zeros."""
    rng = np.random.default_rng(5)
    B, H, D, ps, P = 2, 2, 8, 8, 4
    q = jnp.asarray(rng.standard_normal((B, 3, H, D)).astype(np.float32))
    kp = jnp.asarray(rng.standard_normal((P, ps, H, D)).astype(np.float32))
    vp = jnp.asarray(rng.standard_normal((P, ps, H, D)).astype(np.float32))
    bt = jnp.zeros((B, 0), jnp.int32)
    out = paged_attention(q, kp, vp, bt, block_q=8, interpret=True)
    assert out.shape == (B, 3, H, D) and out.dtype == q.dtype
    assert np.abs(np.asarray(out, np.float32)).max() == 0.0
    # same contract on the quantized path
    from repro.core.quant import quantize_kv_pages
    qk, ks = quantize_kv_pages(kp)
    qv, vs = quantize_kv_pages(vp)
    out_q = paged_attention(q, qk, qv, bt, kv_scales=(ks, vs), block_q=8,
                            interpret=True)
    assert np.abs(np.asarray(out_q, np.float32)).max() == 0.0


def test_int8_pages_dequantize_in_kernel():
    """int8 pools + per-page-per-head scales must match the fp oracle run
    on the DEQUANTIZED pool exactly (up to fp tolerance): the kernel's
    in-fetch dequant is the only thing under test, not the quantization
    error itself."""
    from repro.core.quant import dequantize_kv_pages, quantize_kv_pages
    rng = np.random.default_rng(13)
    B, T, H, Hkv, D, ps = 2, 48, 4, 2, 16, 16
    k, v, kp, vp, bt = build_paged(rng, B, T, Hkv, D, ps)
    q = jnp.asarray(rng.standard_normal((B, 4, H, D)).astype(np.float32))
    qpos = jnp.asarray(np.stack([np.arange(4) + 30, np.arange(4) + 11])
                       .astype(np.int32))
    kvl = jnp.asarray([34, 15], jnp.int32)
    qk, ks = quantize_kv_pages(kp)
    qv, vs = quantize_kv_pages(vp)
    out = paged_attention(q, qk, qv, bt, qpos, kvl, kv_scales=(ks, vs),
                          block_q=8, interpret=True)
    ref = mha_ref(q, gather_pages(dequantize_kv_pages(qk, ks), bt, T),
                  gather_pages(dequantize_kv_pages(qv, vs), bt, T),
                  q_positions=qpos, kv_valid_len=kvl)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=3e-5, rtol=3e-5)


def test_int8_pages_validation():
    """int8 pools without scales, wrong-shape scales, fp pools WITH
    scales, and mixed int8/fp pools must all be rejected loudly."""
    from repro.core.quant import quantize_kv_pages
    rng = np.random.default_rng(17)
    B, T, Hkv, D, ps = 1, 16, 2, 8, 8
    k, v, kp, vp, bt = build_paged(rng, B, T, Hkv, D, ps)
    q = jnp.asarray(rng.standard_normal((B, 2, Hkv, D)).astype(np.float32))
    qk, ks = quantize_kv_pages(kp)
    qv, vs = quantize_kv_pages(vp)
    with pytest.raises(ValueError, match="kv_scales"):
        paged_attention(q, qk, qv, bt, interpret=True)
    with pytest.raises(ValueError, match="shape"):
        paged_attention(q, qk, qv, bt, kv_scales=(ks, vs[:, :1]),
                        interpret=True)
    with pytest.raises(ValueError, match="not int8"):
        paged_attention(q, kp, vp, bt, kv_scales=(ks, vs), interpret=True)
    with pytest.raises(ValueError, match="dtype mismatch"):
        paged_attention(q, qk, vp, bt, kv_scales=(ks, vs), interpret=True)


def test_compiled_kernel_refuses_lane_misaligned_heads():
    """Compiled for a TPU, heads fold into D-wide lane blocks, so a head dim
    that is not a multiple of 128 (smollm-135m's 64) is refused with a
    ValueError naming the cause before Mosaic sees it; interpret mode and a
    single head are exempt."""
    rng = np.random.default_rng(19)
    B, T, Hkv, D, ps = 1, 16, 2, 64, 8
    k, v, kp, vp, bt = build_paged(rng, B, T, Hkv, D, ps)
    q = jnp.asarray(rng.standard_normal((B, 2, Hkv, D)).astype(np.float32))
    with pytest.raises(ValueError, match="multiples of 128"):
        paged_attention(q, kp, vp, bt, interpret=False)
    ref = paged_attention(q, kp, vp, bt, interpret=True)
    assert ref.shape == (B, 2, Hkv, D)
    one = paged_attention(q[:, :, :1], kp[:, :, :1], vp[:, :, :1], bt,
                          interpret=True)
    assert one.shape == (B, 2, 1, D)


def test_soft_cap_and_bf16():
    rng = np.random.default_rng(11)
    B, T, H, D, ps = 1, 32, 2, 16, 16
    k, v, kp, vp, bt = build_paged(rng, B, T, H, D, ps, garbage=3.0)
    q = jnp.asarray(rng.standard_normal((B, 16, H, D)).astype(np.float32))
    for dt, tol in ((jnp.float32, 3e-5), (jnp.bfloat16, 3e-2)):
        out = paged_attention(q.astype(dt), kp.astype(dt), vp.astype(dt),
                              bt, causal=True, soft_cap=5.0, block_q=8,
                              interpret=True,
                              q_positions=jnp.broadcast_to(
                                  jnp.arange(16, dtype=jnp.int32) + 16,
                                  (B, 16)),
                              kv_valid_len=jnp.full((B,), T, jnp.int32))
        ref = mha_ref(q.astype(dt), k.astype(dt), v.astype(dt), causal=True,
                      soft_cap=5.0,
                      q_positions=jnp.broadcast_to(
                          jnp.arange(16, dtype=jnp.int32) + 16, (B, 16)),
                      kv_valid_len=jnp.full((B,), T, jnp.int32))
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=tol, rtol=tol)
