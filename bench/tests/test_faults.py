"""A run with the timed path broken underneath comes out not correct, once
for each fault a one-chip serving cell can have (``bench/faults.py``): a
step that returns its state unchanged, half of the batch left out, a token
altered where it is produced. The harness's look for a chip is skipped
(CPU, smoke size); the rest of the run is the benchmark's."""
import time

import pytest

from bench.faults import FAULTS
from bench.harness import run_cell
from bench.peaks import PEAKS
from bench.tests.smoke import make_root


def run(root, bench, name, hook=None):
    r = run_cell(root, bench, name, 2**40 + 9, 4.0, False,
                 time.perf_counter(), peaks=PEAKS["TPU v5e"],
                 engine_hook=hook)
    r.pop("stderr")
    return r


@pytest.fixture(scope="module")
def one_chip(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench")
    return root, make_root(root)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(one_chip, fault):
    root, bench = one_chip
    assert run(root, bench, "smoke.chat", FAULTS[fault])["correct"] is False


def test_sound_run_is_correct(one_chip):
    root, bench = one_chip
    assert run(root, bench, "smoke.chat")["correct"] is True
