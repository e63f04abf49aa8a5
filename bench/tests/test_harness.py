"""CPU rehearsal of a whole run through ``ServingEngine`` at smoke size:
counts and the correctness verdict only, never a time. The smoke cell is
added the way a later change adds one: a configuration file, a mix file
and an entry, with no edit to a file the benchmark has."""
import hashlib
import time

import pytest

from bench.harness import run_cell
from bench.peaks import PEAKS
from bench.tests.smoke import REPO, make_root

V5E = PEAKS["TPU v5e"]


def digest(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "bench").rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts
            and "tests" not in p.parts} | {
        "BENCHMARK.json": hashlib.sha256(
            (root / "BENCHMARK.json").read_bytes()).hexdigest()}


def run(root, bench, name="smoke.chat", seconds=4.0, trace=False, **kw):
    return run_cell(root, bench, name, 2**40 + 5, seconds, trace,
                    time.perf_counter(), peaks=V5E, **kw)


@pytest.fixture(scope="module")
def smoke(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench")
    before = digest(REPO)
    bench = make_root(root)
    assert digest(REPO) == before       # no committed file was edited
    return root, bench


def test_new_cell_from_files_and_an_entry(smoke):
    root, bench = smoke
    r = run(root, bench)
    info = r.pop("stderr")
    assert r["correct"] is True and r["failed"] == 0
    assert r["attempted"] > 0
    assert set(r["metrics"]) == {"tokens_per_s", "itl_p90_ms", "setup_s"}
    assert list(r)[-1] == "check"
    assert set(r["check"]) == {"mean_gap"}
    assert '"compiles_in_window": 0' in info[0]
    assert info[-1].startswith("check mean_gap ")


def test_traced_run_reports_per_layer_metrics(smoke):
    root, bench = smoke
    r = run(root, bench, trace=True)
    r.pop("stderr")
    assert r["correct"] is True
    # the CPU trace has no TPU ops: device metrics are left out, not 0
    assert {"engine_step_ms_p50", "prefix_hit_share",
            "step_mfu"} <= set(r["metrics"])
    assert "gemm_roofline" not in r["metrics"]
    assert 0 < r["metrics"]["prefix_hit_share"]["value"] <= 100

