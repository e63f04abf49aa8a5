"""core/api.py policy dispatch: all backends agree; batched shapes route
correctly; policy context restores; deprecation shims still work."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import api
from repro.core.plan import GemmPolicy
from repro.kernels.ref import matmul_ref

BACKENDS = ["xla", "blockflow", "pallas"]


def test_default_policy_resolves_xla_on_cpu():
    assert api.current_policy() == GemmPolicy()
    assert api.resolved_backend() == "xla"
    assert api.prefers_einsum()


def test_policy_context_restores():
    with api.use_policy(GemmPolicy(backend="blockflow")):
        assert api.resolved_backend() == "blockflow"
        assert not api.prefers_einsum()
        with api.use_policy(GemmPolicy(backend="pallas")):
            assert api.resolved_backend() == "pallas"
        assert api.resolved_backend() == "blockflow"
    assert api.resolved_backend() == "xla"


@pytest.mark.parametrize("backend", BACKENDS)
def test_backends_agree_2d(backend):
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((96, 128)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((128, 64)).astype(np.float32))
    ref = matmul_ref(a, b)
    out = api.matmul(a, b, policy=GemmPolicy(backend=backend))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-5)


@pytest.mark.parametrize("backend", BACKENDS)
def test_backends_agree_batched_lhs(backend):
    """(B, S, K) @ (K, N) — the layer 'linear' shape."""
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.standard_normal((2, 5, 32)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((32, 16)).astype(np.float32))
    ref = jnp.einsum("bsk,kn->bsn", a, w)
    with api.use_policy(GemmPolicy(backend=backend)):
        out = api.matmul(a, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# Batched-rhs dispatch (b.ndim != 2 → vmap recursion over leading dims)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_batched_rhs_one_lead_dim(backend):
    """(B, M, K) @ (B, K, N) — the attention-scores shape."""
    rng = np.random.default_rng(2)
    a = jnp.asarray(rng.standard_normal((3, 8, 16)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((3, 16, 12)).astype(np.float32))
    ref = jnp.matmul(a, b)
    out = api.matmul(a, b, policy=GemmPolicy(backend=backend))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("backend", BACKENDS)
def test_batched_rhs_two_lead_dims(backend):
    """(B, H, M, K) @ (B, H, K, N) — per-head attention batching."""
    rng = np.random.default_rng(3)
    a = jnp.asarray(rng.standard_normal((2, 4, 8, 16)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((2, 4, 16, 10)).astype(np.float32))
    ref = jnp.matmul(a, b)
    out = api.matmul(a, b, policy=GemmPolicy(backend=backend))
    assert out.shape == (2, 4, 8, 10)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def test_batched_rhs_mismatched_lead_dims_raises():
    a = jnp.zeros((2, 8, 16))
    b = jnp.zeros((3, 16, 4))
    with pytest.raises(AssertionError):
        api.matmul(a, b, policy=GemmPolicy(backend="blockflow"))


def test_linear_bias():
    a = jnp.ones((2, 4))
    w = jnp.ones((4, 3))
    bias = jnp.asarray([1.0, 2.0, 3.0])
    out = api.linear(a, w, bias)
    np.testing.assert_allclose(np.asarray(out[0]), [5.0, 6.0, 7.0])


def test_unknown_backend_raises():
    with pytest.raises(ValueError, match="unknown GEMM backend"):
        api.matmul(jnp.ones((4, 4)), jnp.ones((4, 4)),
                   policy=GemmPolicy(backend="nonesuch"))


def test_model_forward_through_matrixflow_backend():
    """A small model runs end-to-end with every GEMM on the paper's path
    (blockflow on CPU; the Pallas kernel would serve on TPU)."""
    from repro.configs.registry import get_smoke_config
    from repro.models import transformer as T
    cfg = get_smoke_config("smollm-135m", n_layers=1, vocab=64)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jnp.zeros((1, 8), jnp.int32)}
    ref_logits, _, _ = T.forward(params, cfg, batch)
    with api.use_policy(GemmPolicy(backend="blockflow")):
        mf_logits, _, _ = T.forward(params, cfg, batch)
    np.testing.assert_allclose(np.asarray(mf_logits, np.float32),
                               np.asarray(ref_logits, np.float32),
                               atol=5e-2, rtol=5e-2)


# ---------------------------------------------------------------------------
# Deprecation shims (one release)
# ---------------------------------------------------------------------------

def test_gemm_backend_shim_warns_and_pins():
    with pytest.deprecated_call():
        with api.gemm_backend("blockflow"):
            assert api.current_backend() == "blockflow"
    assert api.current_backend() == "xla"


def test_matmul_mode_kw_shim_warns():
    a = jnp.ones((8, 16), jnp.float32)
    b = jnp.ones((16, 8), jnp.float32)
    with pytest.deprecated_call():
        out = api.matmul(a, b, policy=GemmPolicy(backend="blockflow"),
                         mode="dc")
    np.testing.assert_allclose(np.asarray(out), 16.0)
