"""The whole model step's share of the chips' bf16 peak over the window,
in %: the operations the window's served work needs (``bench/flops.py``:
real tokens of every prefill and decode call, the head where a token is
sampled, attention over each token's real context), over window seconds x
chips x peak. Layer: model step."""
from bench import flops as F


def read(run):
    if not run.work:
        return None
    ops = 0.0
    for _, _, pos in run.work:
        w = F.call_work(run.raw, pos, family=run.family)
        ops += w["gemm_flops"] + w["attn_flops"]
    ws, we = run.window
    return 100.0 * ops / ((we - ws) * run.chips * run.peaks.bf16_flops)
