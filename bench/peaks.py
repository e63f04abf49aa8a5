"""Published peaks of each accelerator the benchmark may run on, keyed by
the ``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s
of chip-to-chip interconnect, per chip. A kind that is not here is an
error, never a default: a roofline share against the wrong peaks is a
wrong number.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    bf16_flops: float        # FLOP/s
    int8_ops: float          # OP/s
    hbm_bytes: float         # bytes
    hbm_bytes_per_s: float   # bytes/s
    ici_bytes_per_s: float   # bytes/s, one chip's interconnect
    source: str


_V5E = Peaks(bf16_flops=197e12, int8_ops=393e12, hbm_bytes=16e9,
             hbm_bytes_per_s=819e9, ici_bytes_per_s=1600e9 / 8,
             source='Google Cloud documentation, "TPU v5e"')

PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
