"""Attention backend sweep: fused flash kernel vs the unfused baseline.

Times api.attention under each registered AttentionPolicy backend across the
shapes that dominate serving — prefill (square, GQA) and decode (Sq=1
against a long cache with per-row offsets) — and reports each cell's
correctness (the ``ok``/``max_err`` columns) against
kernels/ref.py::mha_ref, reusing tests/parity.py's attention operands and
tolerances so the numbers can never drift from the parity gate's. The hard
pass/fail gate itself lives in tests/test_parity.py, not here.

On CPU the fused backend runs in interpret mode (a correctness substrate,
not a speed one), so the interesting CPU number is the unfused baseline;
on TPU swap in backend "fused" for the real kernel. ``--backend`` pins one.

  python -m benchmarks.attention_sweep
  python -m benchmarks.attention_sweep --backend unfused --decode-cache 4096
"""
from __future__ import annotations

import argparse
from typing import Optional, Sequence

import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_fn
from repro.core import api
from repro.core.plan import AttentionPolicy


def _load_parity():
    """Import tests/parity.py — the single source of attention operands,
    the mha_ref oracle wiring, and per-dtype tolerances."""
    import importlib
    import os
    import sys
    tests_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tests")
    if tests_dir not in sys.path:
        sys.path.insert(0, tests_dir)
    return importlib.import_module("parity")


def sweep(backends: Sequence[str], dtype: str = "float32",
          decode_cache: int = 512):
    parity = _load_parity()
    cases = list(parity.ATTN_CASES) + [
        # a serving-sized decode cell: full slots, long cache, ragged fills
        parity.AttnCase("decode_serving", B=8, Sq=1, T=decode_cache,
                        H=8, Hkv=2,
                        q_offsets=tuple(
                            (decode_cache * (i + 1)) // 9 for i in range(8)),
                        kv_lens=tuple(
                            (decode_cache * (i + 1)) // 9 + 1
                            for i in range(8))),
        parity.AttnCase("prefill_1k", B=1, Sq=1024, T=1024, H=8, Hkv=2),
    ]
    refs = {}          # oracle per case — backend-independent, compute once
    for backend in backends:
        pol = AttentionPolicy(backend=backend)
        for case in cases:
            q, k, v, qp, kl = parity.make_attention_operands(case, dtype)
            def fn(q=q, k=k, v=v, qp=qp, kl=kl, case=case, pol=pol):
                return api.attention(q, k, v, q_positions=qp,
                                     kv_valid_len=kl, causal=case.causal,
                                     policy=pol)
            t = time_fn(fn, warmup=1, iters=3)
            if case.name not in refs:
                refs[case.name] = np.asarray(parity.mha_ref(
                    q, k, v, causal=case.causal, q_positions=qp,
                    kv_valid_len=kl), np.float32)
            ref = refs[case.name]
            got = np.asarray(fn(), np.float32)
            err = float(np.abs(got - ref).max())
            atol, rtol = parity.ATTN_TOLS[dtype]
            ok = bool(np.allclose(got, ref, atol=atol, rtol=rtol))
            # attention FLOPs ≈ 4·B·H·Sq·T_eff·D (QKᵀ + PV), T_eff = mean
            # valid keys — offsets make the fused kernel's work ragged
            t_eff = float(jnp.mean(jnp.minimum(kl, case.T)))
            flops = 4 * case.B * case.H * case.Sq * t_eff * q.shape[-1]
            emit("attention", f"{backend}_{case.name}_{dtype}",
                 round(t * 1e3, 3), "ms",
                 gflops=round(flops / t / 1e9, 2),
                 max_err=f"{err:.1e}", ok=ok)


def run():
    """Default suite entry (benchmarks.run): CPU-safe backends."""
    sweep(("unfused", "fused"), dtype="float32", decode_cache=256)


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--backend", default=None,
                    help="pin one attention backend (default: unfused + "
                         "fused)")
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--decode-cache", type=int, default=512,
                    help="KV cache length of the serving decode cell")
    args = ap.parse_args(argv)
    backends = ((args.backend,) if args.backend
                else ("unfused", "fused"))
    sweep(backends, dtype=args.dtype, decode_cache=args.decode_cache)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
