"""Operations and bytes that the served work needs, from the model's widths
and the positions each engine call computed.

What counts is the work a request needs, not what the program does: real
tokens only (positions >= 0), never the masked rows and padding columns of a
bucketed batch; the LM head only where a token is sampled (one position per
real row of a call); attention over each token's real causal context.

The count is a dense decoder's. Where a family file (``bench/families/``)
gives ``layer_matmuls(raw)`` or ``kv_bytes_per_token(raw, itemsize)``, its
own count is used in their place; ``family`` is that module, or None.
"""
from __future__ import annotations

from types import ModuleType
from typing import Dict, Optional

import numpy as np


def _dims(raw: Dict):
    D, F, V = raw["hidden_size"], raw["intermediate_size"], raw["vocab_size"]
    L, H = raw["num_hidden_layers"], raw["num_attention_heads"]
    Hkv = raw["num_key_value_heads"]
    dh = raw.get("head_dim") or D // H
    return D, F, V, L, H, Hkv, dh


def layer_matmuls(raw: Dict, family: Optional[ModuleType] = None):
    """(in, out) of each matmul of one layer: q, k, v, o, gate|up, down."""
    if hasattr(family, "layer_matmuls"):
        return family.layer_matmuls(raw)
    D, F, _, _, H, Hkv, dh = _dims(raw)
    return [(D, H * dh), (D, Hkv * dh), (D, Hkv * dh), (H * dh, D),
            (D, 2 * F), (F, D)]


def matmul_params(raw: Dict, family: Optional[ModuleType] = None) -> int:
    """Weights of the layers' matmuls (no embedding, head, bias or norm)."""
    L = raw["num_hidden_layers"]
    return L * sum(i * o for i, o in layer_matmuls(raw, family))


def head_params(raw: Dict) -> int:
    return raw["hidden_size"] * raw["vocab_size"]


def kv_bytes_per_token(raw: Dict, itemsize: int = 2,
                       family: Optional[ModuleType] = None) -> int:
    if hasattr(family, "kv_bytes_per_token"):
        return family.kv_bytes_per_token(raw, itemsize)
    _, _, _, L, _, Hkv, dh = _dims(raw)
    return L * 2 * Hkv * dh * itemsize


def call_work(raw: Dict, positions: np.ndarray, itemsize: int = 2,
              family: Optional[ModuleType] = None) -> Dict[str, float]:
    """Work needed by one engine call over ``positions`` (B, S); -1 marks
    a masked row or a padding column."""
    D, F, V, L, H, Hkv, dh = _dims(raw)
    real = positions >= 0
    n = int(real.sum())
    rows = int(real.any(axis=1).sum())            # one sampled token each
    act = sum(i + o for i, o in layer_matmuls(raw, family)) * L
    params = matmul_params(raw, family)
    gemm_flops = 2.0 * (params * n + head_params(raw) * rows)
    gemm_bytes = itemsize * (params + head_params(raw)
                             + act * n + (D + V) * rows)
    ctx = np.where(real, positions + 1, 0)
    attn_flops = 4.0 * L * H * dh * float(ctx.sum())
    row_ctx = ctx.max(axis=1)                     # each row reads its context
    attn_bytes = (kv_bytes_per_token(raw, itemsize, family)
                  * (float(row_ctx.sum()) + n)
                  + itemsize * 2 * L * H * dh * n)
    return {"tokens": n, "rows": rows, "gemm_flops": gemm_flops,
            "gemm_bytes": float(gemm_bytes), "attn_flops": attn_flops,
            "attn_bytes": float(attn_bytes)}


def least_seconds(flops: float, nbytes: float, peak_flops: float,
                  peak_bw: float) -> float:
    """The least time the chip(s) could take: the larger of the two bounds."""
    return max(flops / peak_flops, nbytes / peak_bw)
