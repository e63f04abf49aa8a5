"""Quickstart: the MatrixFlow public API in five minutes.

  1. a GEMM through the paper's block-major layout + Algorithm 1,
  2. the same GEMM through the Pallas TPU kernel (interpret mode on CPU),
  3. the analytic system model reproducing a paper headline number,
  4. the ExecutionPlan API: GemmPolicy, plan resolution, resident
     PackedWeights, and a tiny transformer forward with every GEMM on
     the MatrixFlow path.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import api
from repro.core import layout as L
from repro.core import sysmodel as SM
from repro.core.blockflow import block_matmul
from repro.core.workloads import PAPER_TABLE3, paper_workload
from repro.kernels.matrixflow_gemm import matrixflow_gemm


def main():
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((256, 512), np.float32))
    b = jnp.asarray(rng.standard_normal((512, 384), np.float32))

    # -- 1. the paper's data structure -------------------------------------
    blk = L.choose_layout(256, 384, 512, jnp.float32, mode="dc")
    print(f"block layout: {blk}  (grid {blk.grid(256, 384, 512)}, "
          f"VMEM claim {blk.vmem_bytes(4) / 1024:.0f} KiB)")
    a_bm = L.to_block_major_a(a, blk.bm, blk.bk)
    print(f"A row-major {a.shape} → block-major {a_bm.shape} "
          f"(each block one contiguous transfer)")

    # -- 2. Algorithm 1, two substrates ------------------------------------
    c_lax = block_matmul(a, b, blk=blk)
    c_pallas = matrixflow_gemm(a, b, blk=blk, interpret=True)
    err = float(jnp.abs(c_lax - c_pallas).max())
    print(f"Algorithm 1 via lax vs Pallas kernel: max |Δ| = {err:.2e}")

    # -- 3. paper headline from the system model ---------------------------
    table = SM.speedup_table(paper_workload("bert-large"), "int32")
    print(f"BERT-large speedup vs 1-core CPU: model {table['mf_dc']:.0f}x, "
          f"paper {PAPER_TABLE3['bert-large']['mf_dc']}x")

    # -- 4. the ExecutionPlan API ------------------------------------------
    # A GemmPolicy is a frozen description of HOW GEMMs execute; plan()
    # resolves it per shape (memoized), consulting the sysmodel for DC/DM.
    policy = api.GemmPolicy(backend="pallas", mode="auto")
    pln = api.plan(256, 384, 512, jnp.float32, policy)
    print(f"plan(256,384,512): backend={pln.backend} mode={pln.mode} "
          f"layout={pln.layout}  (cache: {api.plan_cache_info()})")

    # Weights pack block-major ONCE (the paper's offline arrangement);
    # linear consumes the resident blocks — no per-call re-layout.
    w_packed = api.pack_weight(b, policy)
    y = api.linear(a, w_packed, policy=policy)
    y_row = api.linear(a, b, policy=policy)
    print(f"resident PackedWeight linear: bitwise equal to row-major: "
          f"{bool(jnp.all(y == y_row))}")

    # A model with every GEMM on the MatrixFlow path, weights resident.
    from repro.configs.registry import get_smoke_config
    from repro.models import transformer as T
    cfg = get_smoke_config("smollm-135m", n_layers=2)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jnp.zeros((1, 16), jnp.int32)}
    mf_policy = api.GemmPolicy(backend="blockflow")
    packed_params = api.pack_model_weights(params, mf_policy)
    with api.use_policy(mf_policy):
        t0 = time.perf_counter()
        logits, _, _ = T.forward(packed_params, cfg, batch)
        dt = time.perf_counter() - t0
    print(f"smollm (reduced) forward on the MatrixFlow path: "
          f"logits {logits.shape} in {dt * 1e3:.0f} ms")


if __name__ == "__main__":
    main()
