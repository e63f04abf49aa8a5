"""The readers of the engine's always-on counters (``engine_self_ms``,
``model_row_share``) on hand-built runs, and a traced smoke run whose
profiler trace holds the engine's spans inside the benchmark's ``step``
spans."""
import time

import pytest

from bench import trace as TR
from bench.harness import TRACE_DIR, Run, _load_reader, run_cell
from bench.peaks import PEAKS
from bench.tests.smoke import REPO, make_root

STATS0 = {"tick": 10, "engine_s": 2.0, "engine_wait_s": 1.5,
          "prefill_tokens": 100, "decode_tokens": 50,
          "prefill_rows": 1000, "decode_rows": 60}
STATS1 = {"tick": 14, "engine_s": 6.0, "engine_wait_s": 5.3,
          "prefill_tokens": 356, "decode_tokens": 146,
          "prefill_rows": 1000 + 32 * 256, "decode_rows": 60 + 4 * 32}


def hand_run(stats0, stats1):
    return Run(raw={}, family=None, chips=1, peaks=None,
               window=(0.0, 1.0), clients=None, stats0=stats0,
               stats1=stats1, work=[], reduction=None)


def reader(name):
    return _load_reader(REPO, name)


def test_engine_self_ms_reads_host_work_per_tick():
    # (4.0 s in the engine - 3.8 s waiting) over 4 ticks
    got = reader("engine_self_ms")(hand_run(STATS0, STATS1))
    assert got == pytest.approx(1e3 * 0.2 / 4)


def test_model_row_share_reads_real_tokens_over_rows():
    got = reader("model_row_share")(hand_run(STATS0, STATS1))
    assert got == pytest.approx(100.0 * (256 + 96) / (32 * 256 + 4 * 32))


@pytest.mark.parametrize("name", ["engine_self_ms", "model_row_share"])
def test_reader_without_the_counters_reads_nothing(name):
    old = {k: v for k, v in STATS0.items()
           if k in ("tick", "prefill_tokens", "decode_tokens")}
    assert reader(name)(hand_run(old, old)) is None
    assert reader(name)(hand_run(STATS0, dict(STATS0))) is None   # no work


def test_traced_run_holds_engine_spans_inside_steps(tmp_path):
    root = tmp_path
    bench = make_root(root)
    r = run_cell(root, bench, "smoke.chat", 2**40 + 7, 4.0, True,
                 time.perf_counter(), peaks=PEAKS["TPU v5e"])
    r.pop("stderr")
    assert r["correct"] is True
    m = r["metrics"]
    assert m["engine_self_ms"]["value"] > 0
    assert 0 < m["model_row_share"]["value"] <= 100
    (path,) = (root / TRACE_DIR / "smoke.chat").glob("**/*.xplane.pb")
    host = [e for e in TR.load_xplane(path, keep=TR.relevant)
            if e.plane == TR.HOST_PLANE]
    calls = [e for e in host if e.name in ("step", "submit", "cancel")]
    engine = [e for e in host if e.name.startswith("engine.")]

    def caller(e):
        """The benchmark's span that holds engine span ``e``."""
        return [c.name for c in calls
                if c.start_ns <= e.start_ns and e.end_ns <= c.end_ns]

    assert {"engine.step", "engine.schedule", "engine.prefill",
            "engine.decode", "engine.wait", "engine.emit",
            "engine.admit"} <= {e.name for e in engine}
    assert all(caller(e) for e in engine)
    steps = [e for e in engine if e.name == "engine.step"]
    assert len(steps) == sum(c.name == "step" for c in calls)
    assert all(caller(e) == ["step"] for e in steps)
