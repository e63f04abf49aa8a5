"""Paper Fig. 6 — GEMM performance across data types at 512².

Model layer: speedups per dtype/backend with the paper's MAC-unit PPA
constraints (Table 2: int @1 GHz, fp @600 MHz; fp16 CPU penalty §4.3.2).
Host layer: Pallas kernel (interpret) per dtype vs oracle for throughput
sanity + correctness.

``--dtype <dt>`` drives one dtype end-to-end through the ExecutionPlan
policy path (api.matmul/linear under a GemmPolicy) across every backend;
``--dtype int8`` additionally sweeps the quantized W8A8 weight route
(GemmPolicy(weight_dtype="int8"), resident QuantizedPackedWeight).
"""
from __future__ import annotations

import argparse
from typing import Optional, Sequence

import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_fn
from repro.core import api
from repro.core import sysmodel as SM
from repro.core.plan import GemmPolicy
from repro.kernels.matrixflow_gemm import matrixflow_gemm

POLICY_BACKENDS = ("xla", "blockflow", "pallas")


def _load_parity():
    """Import tests/parity.py — the single source of operands, references,
    and per-dtype tolerances, so the benchmark's pass/fail can never drift
    from the parity gate's."""
    import importlib
    import os
    import sys
    tests_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tests")
    if tests_dir not in sys.path:
        sys.path.insert(0, tests_dir)
    return importlib.import_module("parity")


def run_policy_path(dtype: str, size: int = 256):
    """One dtype through api.matmul/linear under each backend's GemmPolicy —
    the route every model layer takes (plan cache, registry dispatch,
    layouts), not the raw kernel entry points. Timing is measured here;
    correctness per cell is exactly tests/parity.py's differential check."""
    parity = _load_parity()
    shape = (size, size, size)
    a, b = parity.make_operands(dtype, *shape)
    for backend in POLICY_BACKENDS:
        pol = GemmPolicy(backend=backend)
        t = time_fn(lambda: api.matmul(a, b, policy=pol), warmup=1, iters=2)
        try:
            err, ok = parity.check_cell(backend, dtype, shape).max_err, True
        except AssertionError:
            err, ok = float("nan"), False
        emit("fig6_dtype", f"policy_{backend}_{dtype}",
             round(t * 1e3, 2), "ms", max_err=f"{err:.1e}", ok=ok)
    if dtype != "int8":
        return
    # the quantized W8A8 weight route: fp activations, int8 resident weights
    x, w = parity.make_operands("float32", *shape, seed=1)
    for backend in POLICY_BACKENDS:
        pol = GemmPolicy(backend=backend, weight_dtype="int8")
        qw = api.pack_weight(w, pol)           # quantize-at-pack, resident
        t = time_fn(lambda: api.linear(x, qw, policy=pol),
                    warmup=1, iters=2)
        try:
            err, ok = (parity.check_quantized_cell(backend, shape).max_err,
                       True)
        except AssertionError:
            err, ok = float("nan"), False
        emit("fig6_dtype", f"policy_{backend}_w8a8",
             round(t * 1e3, 2), "ms", max_err=f"{err:.1e}", ok=ok)


def run():
    wl = ((SM.Gemm(512, 512, 512),), ())
    for dt in ("int8", "int16", "int32", "fp16", "fp32"):
        t = SM.speedup_table(wl, dt)
        emit("fig6_dtype", f"accel_dc_{dt}", round(t["mf_dc"], 1), "x")
        emit("fig6_dtype", f"neon_{dt}", round(t["neon"], 1), "x")
        emit("fig6_dtype", f"omp_{dt}", round(t["omp"], 1), "x")

    # host-side kernel sweep (correctness + relative cost)
    rng = np.random.default_rng(0)
    for dt, tol in ((jnp.float32, 1e-4), (jnp.bfloat16, 5e-2),
                    (jnp.int8, 0)):
        if dt == jnp.int8:
            a = jnp.asarray(rng.integers(-8, 8, (256, 256)).astype(np.int8))
            b = jnp.asarray(rng.integers(-8, 8, (256, 256)).astype(np.int8))
        else:
            a = jnp.asarray(rng.standard_normal((256, 256),
                                                np.float32)).astype(dt)
            b = jnp.asarray(rng.standard_normal((256, 256),
                                                np.float32)).astype(dt)
        t = time_fn(lambda a=a, b=b: matrixflow_gemm(a, b, interpret=True),
                    warmup=1, iters=2)
        ref = jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32))
        out = matrixflow_gemm(a, b, interpret=True).astype(jnp.float32)
        err = float(jnp.abs(out - ref).max())
        ok = err <= max(tol * float(jnp.abs(ref).max()), 1e-3)
        emit("fig6_dtype", f"kernel_interpret_{jnp.dtype(dt).name}",
             round(t * 1e3, 1), "ms", max_err=f"{err:.1e}", ok=ok)


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dtype", default=None,
                    choices=["float32", "bfloat16", "int8"],
                    help="sweep one dtype through the ExecutionPlan policy "
                         "path instead of the full Fig. 6 table")
    args = ap.parse_args(argv)
    if args.dtype is not None:
        run_policy_path(args.dtype)
    else:
        run()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
