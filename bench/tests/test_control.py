"""The control of the correctness check at a size the CPU holds: the
program with its own int8 path switched on (int8 weights and activations,
int8 KV pages; ``bench/calibrate.py``) must come out not correct on every
seed, and the program as the benchmark runs it correct, under limits set
between the two readings (``smoke.CONTROL_CONFIG``)."""
import time

import pytest

from bench.calibrate import CONTROL
from bench.harness import run_cell
from bench.peaks import PEAKS
from bench.tests.smoke import CONTROL_CONFIG, CONTROL_MIX, make_root


@pytest.fixture(scope="module")
def smoke(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench")
    return root, make_root(root, config=CONTROL_CONFIG, mix=CONTROL_MIX)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_fails_and_program_holds(smoke, seed):
    root, bench = smoke

    def run(lower):
        r = run_cell(root, bench, "smoke.chat", seed, 6.0, False,
                     time.perf_counter(), peaks=PEAKS["TPU v5e"],
                     lower=lower)
        return r["correct"]

    assert run(None) is True
    assert run(CONTROL) is False
