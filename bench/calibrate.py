#!/usr/bin/env python3
"""The readings that a cell's correctness limits are set from.

    python3 bench/calibrate.py --workload <cell> --seconds <s> \\
        --seeds <n> [<n> ...] [--control-seeds <n> ...] \\
        [--faults <name> ... --fault-seeds <n> ...]

In one process (set-up is long, and a chip belongs to one process), for
each seed: a run of the cell as the benchmark makes it; for each control
seed, a run of the control: the program with its own int8 path switched on
(int8 weights and activations, int8 KV pages), the lower precision a later
change might be tempted by; for each fault of ``bench/faults.py`` and each
fault seed, a run with that fault planted under the timed path. Each run
prints one JSON line with the numbers ``bench/check.py`` compares. The
lower reading of a number is the largest a sound run gives; the upper the
smallest the control gives (``PERF.md`` keeps both, and the limit set
between them, in the configuration file's ``correct``).

The program's tensor-parallel path refuses int8 weights
(``ServeConfig.mesh`` with ``weight_dtype``), so for a cell on more than
one chip ``--control-seeds`` stops the run before it touches a device;
its sound runs and faults are read here as on one chip.

The benchmark's own runs never run this.
"""
import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != HERE]


# the program's own int8 paths: W8A8 GEMMs and int8 KV pages
CONTROL = {"weight_dtype": "int8", "kv_dtype": "int8"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--faults", nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from bench.faults import FAULTS
    from bench.harness import configure_jax, find, run_cell

    configure_jax(ROOT)

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = find(bench["workloads"], args.workload, "workload")
    if cell["chips"] > 1 and args.control_seeds:
        print(f"{args.workload} runs on {cell['chips']} chips, and the "
              f"program's TP path refuses the int8 control (ServeConfig."
              f"mesh with weight_dtype): no --control-seeds here",
              file=sys.stderr)
        return 2
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"needs {cell['chips']} TPU chip(s)", file=sys.stderr)
        return 2
    runs = ([("program", s, None, None) for s in args.seeds]
            + [("control", s, CONTROL, None) for s in args.control_seeds]
            + [(f, s, None, FAULTS[f]) for f in args.faults
               for s in args.fault_seeds])
    for kind, seed, lower, fault in runs:
        t0 = time.perf_counter()
        r = run_cell(ROOT, bench, args.workload, seed, args.seconds, False,
                     t0, devices=devices[:cell["chips"]], lower=lower,
                     engine_hook=fault)
        info = json.loads(r["stderr"][0].split(" ", 1)[1])
        print(json.dumps({
            "seed": seed, "run": kind, "correct": r["correct"],
            **info["readings"],
            "compared_tokens": info["compared_tokens"],
            "compared_requests": info["compared_requests"],
            "metrics": {k: v["value"] for k, v in r["metrics"].items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
