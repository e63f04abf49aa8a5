"""Contract sweep: every registered backend × the parity shape/dtype grid.

The executable form of the tentpole claim (docs/analysis.md): for each
GEMM backend in the :mod:`repro.core.plan` registry, each attention
backend, and each architecture in :mod:`repro.configs.registry`, resolve
the concrete block geometry the backend would run — via :func:`plan`
itself for GEMMs, via the kernels' own derivations for attention/SSD —
and run the registered :class:`~repro.analysis.kernel_contracts
.KernelContract` through :func:`check_contract`. Zero violations across
the whole sweep is the acceptance gate CI enforces
(``python -m repro.analysis --all-backends``).

The shape/dtype grids MIRROR ``tests/parity.py`` (SHAPES / DTYPES /
ATTN_CASES / ATTN_PAGE_SIZE): the static pass must cover exactly the
cells the differential harness proves at runtime.
tests/test_analysis.py::test_sweep_grid_matches_parity is the drift
guard — extend both together.
"""
from __future__ import annotations

import dataclasses
import sys
from typing import List, Optional, Sequence, Tuple

import numpy as np

from repro.analysis.kernel_contracts import (ContractViolation,
                                             check_contract,
                                             get_contract_builder)

# -- mirrored from tests/parity.py (drift-guarded there) --------------------
GEMM_SHAPES = (
    (8, 8, 8),
    (64, 96, 48),
    (33, 17, 65),
    (1, 64, 128),
    (130, 24, 56),
)
GEMM_DTYPES = ("float32", "bfloat16", "int8")

# (name, B, Sq, T, H, Hkv) of every tests/parity.py AttnCase.
ATTN_CASES = (
    ("prefill_mha", 2, 32, 32, 4, 4),
    ("prefill_gqa_ragged", 2, 33, 33, 4, 2),
    ("decode_long_cache", 3, 1, 96, 4, 2),
    ("decode_masked_rows", 3, 1, 64, 2, 1),
    ("prefill_chunk_offset", 2, 8, 64, 2, 2),
    ("noncausal_ragged", 2, 17, 45, 2, 1),
)
ATTN_PAGE_SIZE = 16
ATTN_BLOCK = 32                 # block_q/block_k of the parity cells
ATTN_HEAD_DIM = 16


@dataclasses.dataclass(frozen=True)
class SweepEntry:
    """One checked contract instance."""

    kernel: str
    instance: str               # human-readable cell, e.g. "pallas f32 8x8x8"
    violations: Tuple[ContractViolation, ...]

    @property
    def ok(self) -> bool:
        return not self.violations


def _paged_block_tables(B: int, T: int,
                        page_size: int = ATTN_PAGE_SIZE,
                        seed: int = 0, n_distractors: int = 3) -> np.ndarray:
    """The shuffled page assignment of tests/parity.py::make_paged_operands
    (same rng stream), so the checked block table is the one the parity
    cells actually dispatch."""
    nb = -(-T // page_size)
    P = B * nb + n_distractors
    rng = np.random.default_rng(seed * 31 + B * 101 + T)
    return rng.permutation(P)[:B * nb].reshape(B, nb).astype(np.int32)


def sweep_gemm(backends: Optional[Sequence[str]] = None,
               dtypes: Sequence[str] = GEMM_DTYPES,
               shapes: Sequence[Tuple[int, int, int]] = GEMM_SHAPES,
               ) -> List[SweepEntry]:
    """Contract-check every layout-bearing GEMM backend's resolved plans.

    Goes through :func:`repro.core.plan.plan` itself — the sweep validates
    the block choices auto mode actually makes, not hypothetical ones.
    Layout-free backends (xla) have no dataflow contract and are skipped.
    """
    from repro.core import plan as P
    if backends is None:
        P.get_backend_spec("xla")   # force built-in registration
        backends = P.registered_backends()
    entries: List[SweepEntry] = []
    for backend in backends:
        spec = P.get_backend_spec(backend)
        if not spec.needs_layout:
            continue
        builder_name = "blockflow" if backend == "blockflow" \
            else "matrixflow_gemm"
        builder = get_contract_builder(builder_name)
        for dtype in dtypes:
            for (M, K, N) in shapes:
                pol = P.GemmPolicy(backend=backend)
                pln = P.plan(M, N, K, dtype, pol)
                blk = pln.layout
                nbm, nbn, nbk = (-(-M // blk.bm), -(-N // blk.bn),
                                 -(-K // blk.bk))
                if backend == "blockflow":
                    contract = builder(nbm=nbm, nbn=nbn, nbk=nbk)
                else:
                    contract = builder(
                        a_shape=(nbm, nbk, blk.bm, blk.bk),
                        b_shape=(nbn, nbk, blk.bk, blk.bn),
                        blk=blk, fused=(dtype == "int8"))
                entries.append(SweepEntry(
                    builder_name,
                    f"{backend} {dtype} {M}x{K}x{N} "
                    f"blk=({blk.bm},{blk.bn},{blk.bk})/{pln.mode}",
                    tuple(check_contract(contract))))
    return entries


def sweep_attention(cases: Sequence[Tuple] = ATTN_CASES,
                    page_size: int = ATTN_PAGE_SIZE,
                    ) -> List[SweepEntry]:
    """Contract-check the fused and paged attention kernels over the
    parity attention cases — the paged cells against the same shuffled
    block tables the runtime parity cells scatter into."""
    flash = get_contract_builder("flash_attention")
    paged = get_contract_builder("paged_attention")
    entries: List[SweepEntry] = []
    for (name, B, Sq, T, H, Hkv) in cases:
        c = flash(B=B, H=H, Hkv=Hkv, Sq=Sq, Sk=T, D=ATTN_HEAD_DIM,
                  Dv=ATTN_HEAD_DIM, block_q=ATTN_BLOCK, block_k=ATTN_BLOCK)
        entries.append(SweepEntry(
            "flash_attention", f"fused {name}", tuple(check_contract(c))))
        bt = _paged_block_tables(B, T, page_size)
        P_pages = B * bt.shape[1] + 3
        for quantized in (False, True):
            # the parity cells run interpreted (head dim 16)
            c = paged(B=B, Sq=Sq, H=H, Hkv=Hkv, D=ATTN_HEAD_DIM,
                      Dv=ATTN_HEAD_DIM, P=P_pages, page_size=page_size,
                      block_tables=bt, block_q=ATTN_BLOCK,
                      quantized=quantized, interpret=True)
            suffix = " int8-kv" if quantized else ""
            entries.append(SweepEntry(
                "paged_attention", f"paged {name}{suffix}",
                tuple(check_contract(c))))
    return entries


def sweep_configs(archs: Optional[Sequence[str]] = None,
                  seq_len: int = 256) -> List[SweepEntry]:
    """Contract-check every architecture in the configs/ registry: the
    attention geometry (H, Hkv, head_dim) each config serves with, and
    the SSD scan for the SSM/hybrid families."""
    from repro.configs.registry import ARCHS, get_config
    flash = get_contract_builder("flash_attention")
    ssd = get_contract_builder("ssd_scan")
    entries: List[SweepEntry] = []
    for arch in (archs if archs is not None else sorted(ARCHS)):
        cfg = get_config(arch)
        c = flash(B=1, H=cfg.n_heads, Hkv=cfg.n_kv_heads,
                  Sq=128, Sk=seq_len, D=cfg.head_dim, Dv=cfg.head_dim,
                  block_q=128, block_k=128)
        entries.append(SweepEntry(
            "flash_attention",
            f"config {arch} H={cfg.n_heads} Hkv={cfg.n_kv_heads}",
            tuple(check_contract(c))))
        if cfg.ssm_state > 0:
            c = ssd(B=1, S=seq_len, H=cfg.n_heads, P=cfg.head_dim,
                    N=cfg.ssm_state, chunk=128)
            entries.append(SweepEntry(
                "ssd_scan", f"config {arch} N={cfg.ssm_state}",
                tuple(check_contract(c))))
    return entries


def run_sweep(*, gemm_backends: Optional[Sequence[str]] = None,
              dtypes: Sequence[str] = GEMM_DTYPES,
              include_configs: bool = True,
              out=sys.stdout) -> Tuple[List[SweepEntry], int]:
    """The full sweep; prints the violation report and returns
    (entries, total_violations)."""
    entries = sweep_gemm(gemm_backends, dtypes)
    entries += sweep_attention()
    if include_configs:
        entries += sweep_configs()
    n_bad = 0
    for e in entries:
        status = "OK " if e.ok else "FAIL"
        print(f"contract {status} {e.kernel:17s} {e.instance}", file=out)
        for viol in e.violations:
            n_bad += 1
            print(f"  {viol}", file=out)
    print(f"analysis: {len(entries)} contract instances, "
          f"{n_bad} violation(s)", file=out)
    return entries, n_bad
