"""Static-analysis tiers: the checker's own unit tests + the mutation suite.

Three tiers:

  1. **Clean pass** — every built-in contract (all five kernels), the full
     CLI sweep, and the serving engine's traced hot path must produce zero
     violations/findings: the acceptance gate ``python -m repro.analysis
     --all-backends`` enforces in CI.
  2. **Mutation suite** — deliberately corrupted contracts (off-by-one
     index maps, dropped reduction axes, out-of-range block-table entries,
     zero-extent grids, mis-declared semantics...) that the checker must
     each flag with the *right* violation kind. The suite spans every kind
     in ``VIOLATION_KINDS`` — ≥ 6 distinct defect classes caught
     statically, per the PR acceptance criteria.
  3. **Drift guards** — the sweep's mirrored shape/dtype grid must equal
     tests/parity.py's, and the runtime ``require`` guards must raise
     ``ValueError`` (not ``AssertionError``: asserts vanish under -O).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

import parity
from repro.analysis import (ContractViolationError, KernelContract,
                            OperandSpec, Precondition, check_contract,
                            get_contract_builder, lint_jaxpr,
                            registered_contracts, require)
from repro.analysis import sweep as SW
from repro.analysis.kernel_contracts import VIOLATION_KINDS
from repro.core import layout as L
from repro.core.plan import GemmPolicy, plan


def kinds(violations):
    return {v.kind for v in violations}


# ---------------------------------------------------------------------------
# Tier 1: clean pass
# ---------------------------------------------------------------------------

def test_all_five_kernels_register_contracts():
    assert registered_contracts() == (
        "blockflow", "flash_attention", "matrixflow_gemm",
        "paged_attention", "ssd_scan")


GEMM_BLK = L.BlockLayout(bm=8, bn=8, bk=8)


def gemm_contract(**over):
    kw = dict(a_shape=(4, 3, 8, 8), b_shape=(5, 3, 8, 8), blk=GEMM_BLK)
    kw.update(over)
    return get_contract_builder("matrixflow_gemm")(**kw)


def paged_contract(**over):
    kw = dict(B=2, Sq=1, H=4, Hkv=2, D=16, Dv=16, P=8, page_size=16,
              block_tables=np.array([[2, 0, 5], [1, 3, 4]], np.int32),
              block_q=32, interpret=True)
    kw.update(over)
    return get_contract_builder("paged_attention")(**kw)


@pytest.mark.parametrize("name,kwargs", [
    ("matrixflow_gemm", dict(a_shape=(4, 3, 8, 8), b_shape=(5, 3, 8, 8),
                             blk=GEMM_BLK)),
    ("matrixflow_gemm", dict(a_shape=(4, 3, 8, 8), b_shape=(5, 3, 8, 8),
                             blk=GEMM_BLK, fused=True)),
    ("flash_attention", dict(B=2, H=4, Hkv=2, Sq=33, Sk=65, D=16, Dv=16,
                             block_q=32, block_k=32)),
    ("ssd_scan", dict(B=2, S=96, H=3, P=16, N=8, chunk=32)),
    ("blockflow", dict(nbm=3, nbn=4, nbk=2)),
])
def test_builtin_contract_clean(name, kwargs):
    assert check_contract(get_contract_builder(name)(**kwargs)) == []


def test_paged_contract_clean_including_quantized():
    assert check_contract(paged_contract()) == []
    assert check_contract(paged_contract(quantized=True)) == []


def test_paged_contract_lane_tiling_precondition():
    """Compiled (not interpreted), the folded-lane blocks need head dims in
    multiples of 128 unless there is one head: D=16 is a precondition
    violation, D=128 and a single head are clean."""
    v = check_contract(paged_contract(interpret=False))
    assert kinds(v) == {"precondition"}
    assert "TPU lane tiling" in v[0].detail
    assert check_contract(paged_contract(interpret=False, D=128,
                                         Dv=128)) == []
    assert check_contract(paged_contract(interpret=False, H=1,
                                         Hkv=1)) == []


def test_full_sweep_zero_violations():
    """The CI gate, in-process: every backend × dtype × shape plus the
    configs/ registry must contract-check clean."""
    _, n_bad = SW.run_sweep(out=open("/dev/null", "w"))
    assert n_bad == 0


def test_plan_validate_accepts_auto_mode_choices():
    for backend in ("blockflow", "pallas"):
        for (M, K, N) in parity.SHAPES:
            plan(M, N, K, "float32", GemmPolicy(backend=backend),
                 validate=True)       # raises ContractViolationError if bad


# ---------------------------------------------------------------------------
# Tier 2: mutation suite — each seeded defect must be flagged with the
# right violation kind
# ---------------------------------------------------------------------------

def mutate(contract, op_name, **changes):
    """Return the contract with operand ``op_name`` rebuilt with changes."""
    ops = tuple(dataclasses.replace(op, **changes) if op.name == op_name
                else op for op in contract.operands)
    return dataclasses.replace(contract, operands=ops)


def test_mutation_off_by_one_index_map_is_bounds():
    c = mutate(gemm_contract(), "a_bm",
               index_map=lambda i, j, k: (i + 1, k, 0, 0))
    v = check_contract(c)
    assert "bounds" in kinds(v)
    assert any("outside the blocked array" in x.detail for x in v)


def test_mutation_swapped_axes_is_bounds_or_coverage():
    # j has 5 extents but indexes a_bm's 4-block M axis: bounds; and the
    # K stream never advances: coverage.
    c = mutate(gemm_contract(), "a_bm",
               index_map=lambda i, j, k: (j, k, 0, 0))
    assert {"bounds"} <= kinds(check_contract(c))


def test_mutation_missing_reduction_axis_is_write_race():
    c = mutate(gemm_contract(), "c_bm", reduction_axes=())
    v = check_contract(c)
    assert "write_race" in kinds(v)
    assert any("differ along non-reduction axes" in x.detail for x in v)


def test_mutation_dropped_divisibility_guard_is_precondition():
    # b_bm walks a different K stream than a_bm — the guard the kernel
    # used to assert; the checker cites it as a structured precondition.
    c = gemm_contract(b_shape=(5, 2, 8, 8))
    v = check_contract(c)
    assert kinds(v) == {"precondition"}
    assert "K-stream agreement" in v[0].detail


def test_mutation_coverage_hole():
    # the C map pins the N axis to 0: blocks (i, 1..4) are never written.
    c = mutate(gemm_contract(), "c_bm",
               index_map=lambda i, j, k: (i, 0, 0, 0))
    v = check_contract(c)
    assert "coverage" in kinds(v)


def test_mutation_parallel_reduction_axis_is_semantics():
    c = dataclasses.replace(
        gemm_contract(),
        dimension_semantics=("parallel", "parallel", "parallel"))
    v = check_contract(c)
    assert kinds(v) == {"semantics"}
    assert "license to reorder" in v[0].detail


def test_mutation_zero_extent_grid_is_grid():
    """The PR 7 regression class: an empty block table makes the key axis
    zero-extent, the flush step never runs, and the output is returned
    uninitialized. The contract layer refuses it as a precondition (the
    kernel short-circuits nb == 0); the raw grid check catches it too."""
    v = check_contract(paged_contract(
        block_tables=np.zeros((2, 0), np.int32)))
    assert kinds(v) == {"precondition"}
    raw = dataclasses.replace(gemm_contract(), grid=(4, 5, 0))
    assert kinds(check_contract(raw)) == {"grid"}


def test_mutation_out_of_range_block_table_is_bounds():
    """The PR 2 regression class: a block-table entry pointing outside the
    pool (or at another slot's page) is a bad physical fetch the length
    mask cannot save."""
    bt = np.array([[2, 0, 9], [1, 3, 4]], np.int32)       # 9 >= P=8
    v = check_contract(paged_contract(block_tables=bt))
    assert "bounds" in kinds(v)


def test_mutation_non_contiguous_revisit_is_revisit_order():
    # reduction along the OUTERMOST axis: revisits of output block (i, j)
    # are strided by the whole inner grid — flushed, left, re-entered.
    c = KernelContract(
        kernel="mutant", grid=(2, 2, 2),
        operands=(OperandSpec("o", "output", (2, 2), (1, 1),
                              lambda k, i, j: (i, j),
                              reduction_axes=(0,)),),
        dimension_semantics=("arbitrary", "parallel", "parallel"))
    v = check_contract(c)
    assert kinds(v) == {"revisit_order"}


def test_mutation_suite_spans_six_defect_classes():
    """The acceptance criterion: >= 6 distinct defect classes caught."""
    caught = set()
    caught |= kinds(check_contract(mutate(
        gemm_contract(), "a_bm", index_map=lambda i, j, k: (i + 1, k, 0, 0))))
    caught |= kinds(check_contract(mutate(
        gemm_contract(), "c_bm", reduction_axes=())))
    caught |= kinds(check_contract(gemm_contract(b_shape=(5, 2, 8, 8))))
    caught |= kinds(check_contract(mutate(
        gemm_contract(), "c_bm", index_map=lambda i, j, k: (i, 0, 0, 0))))
    caught |= kinds(check_contract(dataclasses.replace(
        gemm_contract(),
        dimension_semantics=("parallel", "parallel", "parallel"))))
    caught |= kinds(check_contract(dataclasses.replace(
        gemm_contract(), grid=(4, 5, 0))))
    caught |= kinds(check_contract(KernelContract(
        kernel="mutant", grid=(2, 2, 2),
        operands=(OperandSpec("o", "output", (2, 2), (1, 1),
                              lambda k, i, j: (i, j), reduction_axes=(0,)),),
        dimension_semantics=("arbitrary", "parallel", "parallel"))))
    assert caught >= set(VIOLATION_KINDS), caught
    assert len(caught) >= 6


# ---------------------------------------------------------------------------
# Tier 3: runtime guards + trace lint + drift guards
# ---------------------------------------------------------------------------

def test_require_raises_value_error_not_assertion():
    with pytest.raises(ValueError, match="broke"):
        require(Precondition.check("x", False, "it broke"),
                Precondition.check("y", True, "fine"))
    require(Precondition.check("y", True, "fine"))        # no raise


def test_kernel_guards_are_value_errors():
    from repro.kernels.flash_attention import flash_attention
    q = jnp.zeros((1, 4, 8, 16))
    kv = jnp.zeros((1, 3, 8, 16))                         # 4 % 3 != 0
    with pytest.raises(ValueError, match="multiple"):
        flash_attention(q, kv, kv, interpret=True)


def test_blockflow_guards_are_value_errors():
    from repro.core.blockflow import block_matmul
    a = jnp.zeros((8, 16))
    b = jnp.zeros((8, 8))                                 # K mismatch
    with pytest.raises(ValueError, match="contraction"):
        block_matmul(a, b)
    b4 = jnp.zeros((1, 2, 8, 8))                          # block-major, no blk
    with pytest.raises(ValueError, match="explicit blk"):
        block_matmul(a, b4)


def test_lint_flags_host_callback():
    def f(x):
        jax.debug.print("x = {}", x)
        return x * 2

    findings = lint_jaxpr(jax.make_jaxpr(f)(jnp.ones(3)))
    assert any(f.rule == "host-callback" for f in findings)


def test_lint_flags_weak_type_input():
    findings = lint_jaxpr(
        jax.make_jaxpr(lambda x, y: x + y)(jnp.ones(3), 1.0))
    assert any(f.rule == "weak-type" for f in findings)


def test_lint_flags_fp64_promotion():
    with jax.enable_x64(True):
        jaxpr = jax.make_jaxpr(
            lambda x: x * np.float64(2.0))(jnp.ones(3, jnp.float32))
    findings = lint_jaxpr(jaxpr, check_weak_invars=False)
    assert any(f.rule == "fp64-promotion" for f in findings)


def test_lint_flags_int8_pool_without_scales():
    def bad(pool):
        def copy(p_ref, o_ref):
            o_ref[...] = p_ref[...]
        return pl.pallas_call(
            copy, out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        )(pool)

    pool = jnp.zeros((4, 16, 2, 8), jnp.int8)             # (P, ps, Hkv, D)
    findings = lint_jaxpr(jax.make_jaxpr(bad)(pool),
                          check_weak_invars=False)
    assert any(f.rule == "int8-pool-no-scales" for f in findings)


def test_lint_recurses_into_jitted_subjaxprs():
    @jax.jit
    def inner(x):
        jax.debug.print("{}", x)
        return x

    findings = lint_jaxpr(jax.make_jaxpr(lambda x: inner(x) + 1)(jnp.ones(3)))
    assert any(f.rule == "host-callback" for f in findings)
    assert any("/jit:" in f.path for f in findings)


def lint_smoke_engine(**serve_kw):
    from repro.analysis.trace_lint import lint_engine
    from repro.configs.registry import get_smoke_config
    from repro.models import transformer as T
    from repro.serving.engine import ServeConfig, ServingEngine

    cfg = get_smoke_config("smollm-135m", n_layers=2, vocab=64)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    sc = ServeConfig(batch_slots=2, max_len=32, **serve_kw)
    return lint_engine(ServingEngine(cfg, params, sc))


def test_serving_engine_hot_path_lints_clean():
    """The jitted prefill/decode closures — the per-request programs — must
    carry no host syncs, fp64 upcasts, weak-type retrace triggers, or
    scale-less int8 pools."""
    assert lint_smoke_engine() == []


def test_serving_engine_int8_pages_lint_clean():
    """The same on int8 pages: the paged kernel consumes int8 pools, so the
    scale rule sees the pools it guards and must find their scales."""
    from repro.core.plan import AttentionPolicy

    assert lint_smoke_engine(
        kv_dtype="int8",
        attention=AttentionPolicy(backend="paged",
                                  page_size=8)) == []


def test_sweep_grid_matches_parity():
    """Drift guard: the static sweep must cover exactly the cells the
    runtime parity harness proves. Extend both together."""
    assert SW.GEMM_SHAPES == parity.SHAPES
    assert SW.GEMM_DTYPES == parity.DTYPES
    assert SW.ATTN_PAGE_SIZE == parity.ATTN_PAGE_SIZE
    mirrored = tuple((c.name, c.B, c.Sq, c.T, c.H, c.Hkv)
                     for c in parity.ATTN_CASES)
    assert SW.ATTN_CASES == mirrored


def test_contract_violation_error_formats_all():
    v = check_contract(gemm_contract(b_shape=(5, 2, 8, 8)))
    err = ContractViolationError(v)
    assert "precondition" in str(err)
    assert err.violations == tuple(v)
