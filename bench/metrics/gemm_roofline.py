"""The GEMM work's share of its roofline, in %: the least time the chips
need for the projection and head GEMMs of every engine call in the window
(``bench/flops.py``: real tokens only, each call's weights read once),
over the mean device time per chip of the ops that do GEMM work — the
MatrixFlow Pallas kernel and XLA's own matmuls alike, so the number reads
the same work whichever implements it. Layer: kernels."""
from bench import flops as F
from bench.trace import GEMM_OPS


def read(run):
    red = run.reduction
    if red is None or not run.work:
        return None
    spent = red.mean_op_s(GEMM_OPS)
    if spent <= 0:
        return None
    p = run.peaks
    least = sum(F.least_seconds(w["gemm_flops"], w["gemm_bytes"],
                                run.chips * p.bf16_flops,
                                run.chips * p.hbm_bytes_per_s)
                for w in (F.call_work(run.raw, pos, family=run.family)
                          for _, _, pos in run.work))
    return 100.0 * least / spent
