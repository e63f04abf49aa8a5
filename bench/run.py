#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. The cell, its configuration and its traffic
are named in ``BENCHMARK.json``. The run builds the model from ``--seed``,
warms up every program the cell drives (set-up), serves its traffic for
``--seconds`` (the window), compares a sample of what it served with the
plain reference, and prints one JSON object as the last line of standard
output: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics; with ``--trace 1`` its per-layer metrics, read from a
profiler trace of the window), ``device`` and, last, ``check``: each
number compared, beside its limit. The same numbers end standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result. JAX's persistent compilation cache is kept in
``.jax_cache/`` inside the checkout, so only a cell's first run compiles.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
# the script's own directory must not shadow modules of the same name
# (``trace``) for anything imported later: the package is ``bench``
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != HERE]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        print(f"no workload {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from bench.harness import configure_jax, print_result, run_cell

    configure_jax(ROOT)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2

    result = run_cell(ROOT, bench, args.workload, args.seed, args.seconds,
                      bool(args.trace), T_START,
                      devices=devices[:cell["chips"]])
    print_result(result)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
