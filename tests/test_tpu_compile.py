"""Compile-only guards: the main-path Pallas kernels at real model widths,
compiled for a described TPU v5e that is not attached.

Nothing runs. The TPU compiler refuses here what interpret mode accepts: a
block shape that breaks the (8, 128) tiling rule, an op Mosaic cannot lower
(``jnp.cumsum``), too much VMEM. Each test asserts that the compiled program
calls the kernel (``tpu_custom_call``), i.e. that it was not interpreted.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file. Widths: qwen2-1.5b (d=1536, H=12, Hkv=2, dh=128,
ff=8960, vocab=151936) for the GEMM and attention kernels, mamba2-1.3b
(64 SSD heads of 64, state 128) for the SSD scan.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.matrixflow_gemm import matrixflow_gemm
from repro.kernels.paged_attention import paged_attention
from repro.kernels.ssd_scan import ssd_scan

H, HKV, DH = 12, 2, 128               # qwen2-1.5b attention
SLOTS, PAGES, PAGE, NB = 8, 2048, 16, 80


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compile cache
    off (a compile for an absent chip can be written but not read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def compile_text(fn, one_chip, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("M,K,N", [
    (1, 1536, 8960),          # decode MLP up-projection
    (4096, 1536, 1536),       # prefill attention projection
    (8, 1536, 151936),        # LM head over the full vocabulary
])
def test_gemm_bf16_compiles(one_chip, M, K, N):
    text = compile_text(lambda a, b: matrixflow_gemm(a, b), one_chip,
                        ((M, K), jnp.bfloat16), ((K, N), jnp.bfloat16))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("M", [1, 4096])
def test_gemm_w8a8_compiles(one_chip, M):
    def w8a8(a, b, sa, sb):
        return matrixflow_gemm(a, b, acc_dtype=jnp.int32, scale_a=sa,
                               scale_b=sb)

    text = compile_text(w8a8, one_chip, ((M, 1536), jnp.int8),
                        ((1536, 8960), jnp.int8), ((M,), jnp.float32),
                        ((8960,), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("Sq,Sk", [(1024, 1024), (1, 1024)],
                         ids=["prefill", "decode"])
def test_flash_attention_compiles(one_chip, Sq, Sk):
    text = compile_text(
        lambda q, k, v, qp, kl: flash_attention(q, k, v, qp, kl), one_chip,
        ((SLOTS, H, Sq, DH), jnp.bfloat16), ((SLOTS, HKV, Sk, DH), jnp.bfloat16),
        ((SLOTS, HKV, Sk, DH), jnp.bfloat16), ((SLOTS, Sq), jnp.int32),
        ((SLOTS,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
@pytest.mark.parametrize("Sq", [1, 256], ids=["decode", "prefill_chunk"])
def test_paged_attention_compiles(one_chip, Sq, kv_dtype):
    shapes = [((SLOTS, Sq, H, DH), jnp.bfloat16),
              ((PAGES, PAGE, HKV, DH), kv_dtype),
              ((PAGES, PAGE, HKV, DH), kv_dtype),
              ((SLOTS, NB), jnp.int32), ((SLOTS, Sq), jnp.int32),
              ((SLOTS,), jnp.int32)]
    if kv_dtype == jnp.int8:
        shapes += [((PAGES, HKV), jnp.float32)] * 2

        def fn(q, k, v, bt, qp, kl, ks, vs):
            return paged_attention(q, k, v, bt, qp, kl, kv_scales=(ks, vs))
    else:
        def fn(q, k, v, bt, qp, kl):
            return paged_attention(q, k, v, bt, qp, kl)
    assert "tpu_custom_call" in compile_text(fn, one_chip, *shapes)


def test_ssd_scan_compiles(one_chip):
    B, S, Hs, P, N = 1, 1024, 64, 64, 128
    text = compile_text(
        lambda x, dt, A, b, c: ssd_scan(x, dt, A, b, c), one_chip,
        ((B, S, Hs, P), jnp.bfloat16), ((B, S, Hs), jnp.float32),
        ((Hs,), jnp.float32), ((B, S, N), jnp.bfloat16),
        ((B, S, N), jnp.bfloat16))
    assert "tpu_custom_call" in text
