"""The cases of ``test_chips.py`` that need four devices. Each runs in a
child process that XLA_FLAGS gives four CPU devices before JAX starts:

    python -m bench.tests.multichip weights
    python -m bench.tests.multichip cell <root> <workload>

and prints one JSON object as its last line.
"""
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np

from bench import check as C
from bench.harness import find, run_cell
from bench.model import (init_weights, load_config, load_family,
                         program_config, tp_context)
from bench.peaks import PEAKS
from bench.tests.smoke import REPO, SEED, SMOKE_CONFIG


def weights() -> dict:
    """SMOKE_CONFIG's weights made sharded over four devices against those
    made on one."""
    fam = load_family(REPO, SMOKE_CONFIG)
    cfg = program_config(SMOKE_CONFIG, fam)
    tp = tp_context(cfg, jax.devices()[:4])
    one = jax.tree_util.tree_leaves(init_weights(cfg, fam, SEED))
    four = jax.tree_util.tree_leaves(init_weights(cfg, fam, SEED, tp))
    return {
        "devices": len(jax.devices()),
        "equal": all(np.array_equal(np.asarray(a).view(np.uint16),
                                    np.asarray(b).view(np.uint16))
                     for a, b in zip(one, four)) and len(one) == len(four),
        "sharded_leaves": sum(not x.sharding.is_fully_replicated
                              for x in four)}


def cell(root: Path, workload: str) -> dict:
    """A run of ``workload``, and its reference's gaps for the sample it
    compared, over the cell's devices against over one device."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    seen = {}
    readings = C.readings

    def keep(ref, w, requests):
        seen.update(ref=ref, w=w, requests=list(requests))
        return readings(ref, w, requests)

    C.readings = keep
    r = run_cell(root, bench, workload, SEED, 4.0, False,
                 time.perf_counter(), peaks=PEAKS["TPU v5e"])
    C.readings = readings
    entry = find(bench["configs"], find(bench["workloads"], workload,
                                        "workload")["config"], "config")
    raw = load_config(root, entry)
    fam = load_family(root, raw)
    one = init_weights(program_config(raw, fam), fam, SEED)
    ref, w = seen["ref"], seen["w"]
    diff = max(float(np.abs(ref.token_gaps(w, q.prompt, q.tokens)
                            - ref.token_gaps(one, q.prompt, q.tokens)).max())
               for q in seen["requests"])
    return {"correct": r["correct"], "device": r["device"],
            "check": r["check"], "compared": len(seen["requests"]),
            "sharded_leaves": sum(not x.sharding.is_fully_replicated
                                  for x in jax.tree_util.tree_leaves(w)),
            "gap_diff": diff}


if __name__ == "__main__":
    case, *args = sys.argv[1:]
    if case == "weights":
        out = weights()
    else:
        out = cell(Path(args[0]), args[1])
    print(json.dumps(out), flush=True)
