"""The paged attention kernel's share of its roofline, in %: the least
time the chips need for attention over each token's real context (K/V
bytes of the real contexts and their FLOPs, ``bench/flops.py``), over the
mean device time per chip of the ``paged_attention`` kernel and any XLA
attention op. Layer: kernels."""
from bench import flops as F
from bench.trace import ATTENTION_OPS


def read(run):
    red = run.reduction
    if red is None or not run.work:
        return None
    spent = red.mean_op_s(ATTENTION_OPS)
    if spent <= 0:
        return None
    p = run.peaks
    least = sum(F.least_seconds(w["attn_flops"], w["attn_bytes"],
                                run.chips * p.bf16_flops,
                                run.chips * p.hbm_bytes_per_s)
                for w in (F.call_work(run.raw, pos, family=run.family)
                          for _, _, pos in run.work))
    return 100.0 * least / spent
