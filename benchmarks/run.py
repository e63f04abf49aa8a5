"""Benchmark driver: one module per paper table/figure, plus the roofline
table from the dry-run artifacts. Emits benchmarks/results.csv.

  python -m benchmarks.run               # all
  python -m benchmarks.run fig7 table3   # subset
"""
from __future__ import annotations

import os
import sys

from benchmarks import (attention_sweep, gemm_dtype_sweep, gemm_size_sweep,
                        interconnect_sweep, roofline_table, runtime_breakdown,
                        serving_sweep, transformer_e2e)
from benchmarks.common import dump_csv
from repro.launch.compile_cache import use_compile_cache

SUITES = {
    "fig7": gemm_size_sweep.run,
    "fig6": gemm_dtype_sweep.run,
    "table3": transformer_e2e.run,
    "fig8": runtime_breakdown.run,
    "fig9": interconnect_sweep.run,
    "roofline": roofline_table.run,
    "attention": attention_sweep.run,
    "serving": serving_sweep.run,
    # TP column: paged serving over a (data, model) host mesh (skips with
    # a message on 1-device hosts; force devices via XLA_FLAGS)
    "serving-tp": serving_sweep.run_tp,
    # prefix-cache acceptance: shared-prefix + bursty Poisson mixes with
    # and without COW prompt-page sharing at a fixed pool size
    "serving-prefix": serving_sweep.run_prefix,
    # quantized-KV capacity: bf16 vs int8 KV pages at an equal pool-byte
    # budget (gate: >=1.8x peak resident requests under int8)
    "serving-kv": serving_sweep.run_kv,
    # speculative decoding: greedy vs n-gram self-speculation at
    # token-identical streams (gate: >=1.5x tokens/s for the spec cell)
    "serving-spec": serving_sweep.run_spec,
}


def main(argv=None) -> int:
    argv = argv if argv is not None else sys.argv[1:]
    use_compile_cache()
    picks = [a for a in argv if not a.startswith("-")] or list(SUITES)
    for name in picks:
        print(f"\n===== {name} =====")
        SUITES[name]()
    out = os.path.join(os.path.dirname(__file__), "results.csv")
    dump_csv(out)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
