"""ExecutionPlan API (core/plan.py): policy resolution + memoized plan
cache, the backend registry, and resident block-major PackedWeights."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import api
from repro.core import layout as L
from repro.core import plan as P
from repro.core.plan import ExecutionPlan, GemmPolicy, PackedWeight


# ---------------------------------------------------------------------------
# Plan resolution + cache
# ---------------------------------------------------------------------------

def test_plan_cache_hits_on_repeated_shapes():
    P.plan_cache_clear()
    pol = GemmPolicy(backend="blockflow", mode="dm")
    p1 = P.plan(128, 256, 512, jnp.float32, pol)
    miss_info = P.plan_cache_info()
    p2 = P.plan(128, 256, 512, jnp.float32, pol)
    hit_info = P.plan_cache_info()
    assert miss_info.misses == 1 and miss_info.hits == 0
    assert hit_info.hits == 1 and hit_info.misses == 1
    assert p1 is p2                      # memoized: the same object

    # a different policy is a different cache entry
    P.plan(128, 256, 512, jnp.float32, GemmPolicy(backend="blockflow",
                                                  mode="dc"))
    assert P.plan_cache_info().misses == 2


def test_plan_resolves_layout_and_acc():
    pln = P.plan(64, 384, 256, jnp.bfloat16,
                 GemmPolicy(backend="pallas", mode="dm"))
    assert isinstance(pln, ExecutionPlan)
    assert pln.backend == "pallas"
    assert pln.mode == "dm"
    assert pln.layout.mode == "dm"
    assert pln.acc == jnp.dtype(jnp.float32)
    assert pln.layout.vmem_bytes(2) <= GemmPolicy().vmem_budget

    int_pln = P.plan(64, 64, 64, jnp.int8, GemmPolicy(backend="blockflow"))
    assert int_pln.acc == jnp.dtype(jnp.int32)


def test_plan_auto_mode_consults_sysmodel():
    """mode="auto" must resolve to a concrete paper mode per shape, matching
    the sysmodel's own dc-vs-dm comparison."""
    from repro.core import sysmodel as SM
    pol = GemmPolicy(backend="blockflow", mode="auto")
    for M, N, K in [(128, 128, 128), (1024, 1024, 1024), (8192, 512, 512)]:
        pln = P.plan(M, N, K, jnp.float32, pol)
        g = SM.Gemm(M=M, K=K, N=N)
        t_dc = SM.matrixflow_gemm_time(g, "fp32", mode="dc")["total"]
        t_dm = SM.matrixflow_gemm_time(g, "fp32", mode="dm")["total"]
        expect = "dc" if t_dc <= t_dm else "dm"
        assert pln.mode == expect, (M, N, K)


def test_plan_layout_override_skips_choice():
    blk = L.BlockLayout(16, 128, 128, "dc")
    pln = P.plan(999, 999, 999, jnp.float32,
                 GemmPolicy(backend="blockflow", layout=blk))
    assert pln.layout is blk
    assert pln.mode == "dc"


def test_xla_plan_needs_no_layout():
    pln = P.plan(64, 64, 64, jnp.float32, GemmPolicy(backend="xla"))
    assert pln.layout is None and pln.mode is None


def test_acc_dtype_override():
    a = jnp.ones((8, 16), jnp.bfloat16)
    b = jnp.ones((16, 8), jnp.bfloat16)
    pol = GemmPolicy(backend="blockflow", acc_dtype="float32")
    out = api.matmul(a, b, policy=pol, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), 16.0)


# ---------------------------------------------------------------------------
# Backend registry
# ---------------------------------------------------------------------------

def test_register_backend_dispatch():
    calls = []

    def fake_gemm(a2, b, pln, out_dtype):
        calls.append((a2.shape, pln.backend))
        return jnp.zeros((a2.shape[0], b.shape[-1]), out_dtype)

    P.register_backend("fake", fake_gemm, overwrite=True)
    try:
        out = api.matmul(jnp.ones((4, 8)), jnp.ones((8, 6)),
                         policy=GemmPolicy(backend="fake"))
        assert out.shape == (4, 6)
        assert calls == [((4, 8), "fake")]
    finally:
        P.unregister_backend("fake")
    with pytest.raises(ValueError):
        P.get_backend_spec("fake")


def test_register_backend_no_silent_overwrite():
    P.register_backend("dupe", lambda *a: None, overwrite=True)
    try:
        with pytest.raises(ValueError, match="already registered"):
            P.register_backend("dupe", lambda *a: None)
    finally:
        P.unregister_backend("dupe")


def test_builtin_backends_present():
    names = P.registered_backends()
    for expected in ("xla", "pallas", "blockflow"):
        assert expected in names


# ---------------------------------------------------------------------------
# PackedWeight: resident block-major weights
# ---------------------------------------------------------------------------

def test_pack_weight_roundtrip():
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((200, 136)).astype(np.float32))
    pw = P.pack_weight(w, GemmPolicy(backend="blockflow", mode="dm"))
    assert pw.shape == (200, 136)
    assert pw.data.shape == (L.cdiv(136, pw.bn), L.cdiv(200, pw.bk),
                             pw.bk, pw.bn)
    np.testing.assert_array_equal(np.asarray(pw.unpack()), np.asarray(w))


def test_packed_linear_bitwise_identical_pallas_interpret():
    """Acceptance: linear with a PackedWeight is bitwise-identical to the
    row-major path under pallas (interpreted off-TPU) — same kernel, same
    blocks, minus the per-call re-layout."""
    rng = np.random.default_rng(1)
    pol = GemmPolicy(backend="pallas", mode="dm")
    x = jnp.asarray(rng.standard_normal((64, 256)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((256, 384)).astype(np.float32))
    y_row = api.linear(x, w, policy=pol)
    y_packed = api.linear(x, P.pack_weight(w, pol), policy=pol)
    np.testing.assert_array_equal(np.asarray(y_row), np.asarray(y_packed))


@pytest.mark.parametrize("backend", ["xla", "blockflow"])
def test_packed_linear_other_backends(backend):
    """Layout-free backends unpack transparently — same numerics."""
    rng = np.random.default_rng(2)
    pol = GemmPolicy(backend=backend, mode="dm")
    x = jnp.asarray(rng.standard_normal((16, 96)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((96, 40)).astype(np.float32))
    y_row = api.linear(x, w, policy=pol)
    y_packed = api.linear(x, P.pack_weight(w, pol), policy=pol)
    np.testing.assert_array_equal(np.asarray(y_row), np.asarray(y_packed))


def test_packed_weight_is_pytree():
    w = jnp.ones((32, 16))
    pw = P.pack_weight(w, GemmPolicy(mode="dm"))
    leaves, treedef = jax.tree_util.tree_flatten(pw)
    assert len(leaves) == 1                      # geometry is static aux
    pw2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert (pw2.k, pw2.n, pw2.bk, pw2.bn) == (pw.k, pw.n, pw.bk, pw.bn)
    # tree_map over the data leaf (what lax.scan / _index_tree do)
    doubled = jax.tree_util.tree_map(lambda t: t * 2, pw)
    np.testing.assert_array_equal(np.asarray(doubled.unpack()),
                                  2 * np.asarray(pw.unpack()))


def test_pack_model_weights_model_equivalence():
    """pack_model_weights packs projections, skips MoE expert banks, and the
    packed model matches the row-major model on the MatrixFlow path."""
    from repro.configs.registry import get_smoke_config
    from repro.models import transformer as T
    cfg = get_smoke_config("smollm-135m", n_layers=2, vocab=64)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    pol = GemmPolicy(backend="blockflow", mode="dm")
    packed = P.pack_model_weights(params, pol)
    assert isinstance(packed["head"], PackedWeight)
    assert isinstance(packed["layers"]["attn"]["wq"], PackedWeight)
    # norm scales and embeddings pass through untouched
    assert not isinstance(packed["embed"], PackedWeight)
    assert not isinstance(packed["final_norm"]["scale"], PackedWeight)

    batch = {"tokens": jnp.zeros((1, 8), jnp.int32)}
    with api.use_policy(pol):
        ref_logits, _, _ = T.forward(params, cfg, batch)
        packed_logits, _, _ = T.forward(packed, cfg, batch)
    np.testing.assert_array_equal(np.asarray(ref_logits),
                                  np.asarray(packed_logits))


def test_pack_model_weights_skips_moe_banks():
    pol = GemmPolicy(backend="blockflow")
    tree = {"moe": {"wi": jnp.ones((4, 8, 16)), "wo": jnp.ones((4, 16, 8)),
                    "router": jnp.ones((8, 4)),
                    "shared": {"wi": jnp.ones((8, 32))}},
            "attn": {"wq": jnp.ones((8, 8))}}
    packed = P.pack_model_weights(tree, pol)
    assert not isinstance(packed["moe"]["wi"], PackedWeight)
    assert not isinstance(packed["moe"]["wo"], PackedWeight)
    assert isinstance(packed["moe"]["router"], PackedWeight)
    assert isinstance(packed["moe"]["shared"]["wi"], PackedWeight)
    assert isinstance(packed["attn"]["wq"], PackedWeight)


def test_layout_for_packed_respects_calling_budget():
    """A weight packed under one policy, consumed under a tighter one: bm
    shrinks to honor the caller's vmem_budget; an impossible fit raises a
    named error instead of silently overflowing VMEM."""
    w = jnp.ones((2048, 512), jnp.float32)
    pw = P.pack_weight(w, GemmPolicy(mode="dm"))     # bk=2048, bn=512
    mid = GemmPolicy(backend="pallas", mode="dc",
                     vmem_budget=12 * 1024 * 1024)
    blk = P.layout_for_packed(512, pw, jnp.float32, mid)
    assert (blk.bk, blk.bn) == (pw.bk, pw.bn)
    assert blk.vmem_bytes(4) <= mid.vmem_budget
    tight = GemmPolicy(backend="pallas", mode="dc",
                       vmem_budget=2 * 1024 * 1024)
    with pytest.raises(ValueError, match="cannot fit"):
        P.layout_for_packed(512, pw, jnp.float32, tight)


def test_plan_module_usable_standalone():
    """plan.py must not depend on api.py having been imported first (the
    built-ins lazily register on first lookup)."""
    import os
    import subprocess
    import sys
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = os.path.join(repo_root, "src")
    code = ("from repro.core import plan as P; import jax.numpy as jnp; "
            "assert P.plan(64, 64, 64, jnp.float32).backend == 'xla'")
    r = subprocess.run([sys.executable, "-c", code],
                       env=dict(os.environ, PYTHONPATH=src,
                                JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, cwd=repo_root)
    assert r.returncode == 0, r.stderr


def test_policy_is_hashable_and_frozen():
    pol = GemmPolicy(backend="blockflow", mode="dc")
    assert hash(pol) == hash(dataclasses.replace(pol))
    with pytest.raises(dataclasses.FrozenInstanceError):
        pol.backend = "xla"


# ---------------------------------------------------------------------------
# AttentionPolicy + attention backend registry
# ---------------------------------------------------------------------------

def test_attention_policy_hashable_and_frozen():
    from repro.core.plan import AttentionPolicy
    pol = AttentionPolicy(backend="fused", block_q=64)
    assert hash(pol) == hash(dataclasses.replace(pol))
    with pytest.raises(dataclasses.FrozenInstanceError):
        pol.backend = "unfused"


def test_attention_auto_resolution_mirrors_gemm():
    """"auto" resolves per platform like the GEMM registry: fused on TPU,
    unfused elsewhere; explicit names pass through untouched."""
    from repro.core.plan import AttentionPolicy, resolve_attention_backend
    expect = "fused" if jax.default_backend() == "tpu" else "unfused"
    assert resolve_attention_backend("auto") == expect
    assert AttentionPolicy().resolved_backend() == expect
    assert resolve_attention_backend("fused") == "fused"


def test_attention_registry_builtins_and_errors():
    assert {"fused", "paged", "unfused"} <= set(
        P.registered_attention_backends())
    with pytest.raises(ValueError, match="already registered"):
        P.register_attention_backend("unfused", lambda *a, **k: None)
    with pytest.raises(ValueError, match="unknown attention backend"):
        P.get_attention_backend_spec("no-such-attn")


def test_attention_registry_custom_backend_dispatch():
    """A registered backend receives the full offset/length contract and
    its output is returned untouched — downstream paged/sharded attention
    implementations plug in without touching dispatch."""
    from repro.core.plan import AttentionPolicy
    seen = {}

    def fake(q, k, v, *, q_positions, kv_valid_len, causal, scale, soft_cap,
             policy):
        seen.update(causal=causal, scale=scale, policy=policy)
        return jnp.zeros(q.shape[:3] + (v.shape[-1],), q.dtype)

    P.register_attention_backend("fake_attn", fake)
    try:
        q = jnp.ones((1, 4, 2, 8)); kv = jnp.ones((1, 4, 1, 8))
        pol = AttentionPolicy(backend="fake_attn")
        out = api.attention(q, kv, kv,
                            q_positions=jnp.zeros((1, 4), jnp.int32),
                            kv_valid_len=jnp.full((1,), 4, jnp.int32),
                            policy=pol)
        assert out.shape == (1, 4, 2, 8)
        assert seen["causal"] is True and seen["policy"] is pol
        assert seen["scale"] == pytest.approx(8 ** -0.5)
    finally:
        P.unregister_attention_backend("fake_attn")


def test_use_attention_policy_nests_thread_local():
    from repro.core.plan import AttentionPolicy
    base = api.current_attention_policy()
    inner = AttentionPolicy(backend="fused", block_q=32)
    with api.use_attention_policy(inner):
        assert api.current_attention_policy() is inner
        with api.use_attention_policy(AttentionPolicy(backend="unfused")):
            assert api.resolved_attention_backend() == "unfused"
        assert api.current_attention_policy() is inner
    assert api.current_attention_policy() == base
