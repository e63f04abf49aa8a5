"""Observability (repro/obs): metrics registry, Perfetto tracing,
engine spans on the profiler's clock, per-request lifecycle records,
null-mode zero-cost, engine integration."""
import gc
import json

import jax
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.core.plan import AttentionPolicy
from repro.models import transformer as T
from repro.obs import (
    NULL_OBS,
    PHASE_TRACKS,
    Histogram,
    Metrics,
    Observability,
    Timer,
    TraceRecorder,
    aggregate_request_traces,
    install_gc_span,
    merge_histograms,
    quantile,
    validate_metrics_snapshot,
    validate_trace,
)
from repro.serving.engine import ServeConfig, ServingEngine
from repro.serving.scheduler import Scheduler

PAGED8 = AttentionPolicy(backend="paged", page_size=8, block_q=8)


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("smollm-135m", n_layers=2, vocab=64)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


# -- metrics ----------------------------------------------------------------

def test_counter_gauge_basics():
    m = Metrics()
    c = m.counter("reqs_total", kind="fresh")
    c.inc()
    c.inc(3)
    assert c.value == 4
    # memoized: same (name, labels) → same instrument
    assert m.counter("reqs_total", kind="fresh") is c
    assert m.counter("reqs_total", kind="resume") is not c
    g = m.gauge("pool_free")
    g.set(7)
    g.set_max(3)          # set_max never lowers
    assert g.value == 7
    g.set_max(11)
    assert g.value == 11


def test_metric_kind_conflict_raises():
    m = Metrics()
    m.counter("x_total")
    with pytest.raises(ValueError):
        m.gauge("x_total")


def test_histogram_quantile_and_exact_quantile():
    h = Histogram("lat_s", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0):
        h.observe(v)
    assert h.count == 4
    assert h.mean() == pytest.approx((0.05 + 0.5 + 0.5 + 5.0) / 4)
    assert 0.0 <= h.quantile(0.5) <= 1.0     # inside the 0.1–1.0 bucket
    # exact quantile over raw samples (the SLO path)
    assert quantile([1.0, 2.0, 3.0, 4.0], 0.5) == pytest.approx(2.5)
    assert quantile([1.0], 0.99) == 1.0


def test_histogram_merge_associative():
    buckets = (0.01, 0.1, 1.0)
    rng = np.random.default_rng(0)
    hs = []
    for _ in range(3):
        h = Histogram("t_s", buckets=buckets)
        for v in rng.exponential(0.1, 50):
            h.observe(float(v))
        hs.append(h)
    a, b, c = hs
    left = a.merge(b).merge(c).snapshot()
    right = a.merge(b.merge(c)).snapshot()
    assert left == right
    assert merge_histograms(hs).snapshot() == left
    # operands untouched
    assert a.count == 50


def test_histogram_merge_bucket_mismatch_raises():
    with pytest.raises(ValueError):
        Histogram("a", buckets=(1.0,)).merge(Histogram("a", buckets=(2.0,)))


def test_metrics_snapshot_schema_and_roundtrip():
    m = Metrics()
    m.counter("hits_total", cache="prefix").inc(2)
    m.gauge("free_pages").set(5)
    m.histogram("step_s").observe(0.01)
    snap = m.snapshot()
    assert validate_metrics_snapshot(snap) == []
    assert snap == json.loads(json.dumps(snap))
    assert snap["counters"]["hits_total{cache=prefix}"] == 2
    assert snap["gauges"]["free_pages"] == 5
    assert snap["histograms"]["step_s"]["count"] == 1


def test_timer():
    with Timer() as tm:
        sum(range(1000))
    assert tm.dt > 0.0
    assert tm.ms == pytest.approx(tm.dt * 1e3)


# -- tracing ----------------------------------------------------------------

def test_trace_export_valid_and_balanced():
    tr = TraceRecorder()
    t0 = tr.epoch
    tr.complete("engine.decode", "engine.decode", t0, t0 + 0.001,
                args={"slots": 2})
    tr.instant("engine.evict", "evict 3p")
    tr.async_begin(7, {"prompt_len": 4})
    tr.async_instant(7, "first-token")
    tr.async_end(7, {"n_tokens": 5})
    doc = tr.export()
    assert validate_trace(doc) == []
    evs = doc["traceEvents"]
    # one metadata thread row per phase track, in PHASE_TRACKS order
    names = [e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "thread_name"]
    assert list(PHASE_TRACKS) == names[:len(PHASE_TRACKS)]
    b = [e for e in evs if e["ph"] == "b"]
    e_ = [e for e in evs if e["ph"] == "e"]
    assert len(b) == len(e_) == 1
    assert b[0]["id"] == e_[0]["id"] == "7"
    assert b[0]["cat"] == "request"


def test_trace_auto_closes_open_async_spans():
    tr = TraceRecorder()
    tr.async_begin(3)
    tr.async_instant(3, "first-token")
    doc = tr.export()                      # request still in flight
    assert validate_trace(doc) == []       # exporter balanced it
    closes = [e for e in doc["traceEvents"] if e["ph"] == "e"]
    assert len(closes) == 1
    assert closes[0]["args"]["truncated"] is True


def test_trace_ring_drops_oldest():
    tr = TraceRecorder(capacity=4)
    for i in range(10):
        tr.instant("admit", f"ev{i}")
    assert len(tr) == 4
    assert tr.dropped == 6
    assert validate_trace(tr.export()) == []


def test_validate_trace_catches_imbalance():
    bad = {"traceEvents": [
        {"ph": "b", "cat": "request", "id": "1", "name": "req 1",
         "pid": 1, "ts": 0.0}]}
    assert validate_trace(bad) != []


# -- null mode --------------------------------------------------------------

def test_null_obs_records_nothing(setup):
    cfg, params = setup
    eng = ServingEngine(cfg, params, ServeConfig(
        batch_slots=2, max_len=32, attention=PAGED8,
        scheduler=Scheduler(prefill_chunk=2)))   # default NULL_OBS
    assert eng.obs is NULL_OBS
    h = eng.submit([1, 2, 3])
    for _ in range(4):
        eng.step()
    assert eng.cancel(h) is True
    # every span site ran (the always-on counters moved) ...
    st = eng.stats()
    assert st["prefill_rows"] > 0 and st["decode_rows"] > 0
    assert st["engine_wait_s"] > 0
    # ... and none of them recorded anything outside the profiler
    assert len(eng.obs.trace) == 0
    snap = eng.obs.metrics.snapshot()
    assert all(v == {} for v in snap.values())   # no series registered
    assert eng.request_traces == {}        # no per-request allocation
    # null instruments and recorder are shared no-op singletons
    assert eng.obs.metrics.counter("a") is eng.obs.metrics.gauge("b")
    assert eng.obs.trace.export()["traceEvents"] == []


# -- engine integration -----------------------------------------------------

def test_request_trace_token_exact_across_preempt_resume(setup):
    """The tentpole contract: a preempted+resumed request's trace holds
    exactly the tokens the engine reported — and records the preemption —
    while the trace export stays schema-valid."""
    cfg, params = setup
    sc = ServeConfig(batch_slots=2, max_len=16, attention=PAGED8,
                     cache_pages=2,        # half the padded need → pressure
                     obs=Observability())
    eng = ServingEngine(cfg, params, sc)
    prompts = [[1, 2, 3], [4, 5, 6]]
    rids = [eng.submit(p) for p in prompts]
    streams = {r: [] for r in rids}
    for _ in range(60):
        for h, t in eng.step().items():
            streams[h].append(t)
        if not eng.slot_live.any() and not eng.wait:
            break
    assert eng.n_preemptions > 0
    preempted = 0
    for r in rids:
        rt = eng.request_trace(r)
        assert rt is not None
        assert rt.tokens == streams[r]                 # token-exact
        assert rt.ttft_s() is not None and rt.ttft_s() > 0
        assert rt.retire_s is not None
        assert rt.prompt_len == 3
        assert len(rt.itl_list()) == len(rt.tokens) - 1
        assert all(g >= 0 for g in rt.itl_list())
        assert rt.pages_timeline                       # pages were tracked
        preempted += rt.n_preemptions
        assert json.loads(json.dumps(rt.to_json())) == rt.to_json()
    assert preempted == eng.n_preemptions
    agg = aggregate_request_traces(
        [eng.request_trace(r) for r in rids])
    assert agg["n_requests"] == 2
    assert agg["total_tokens"] == sum(len(s) for s in streams.values())
    assert agg["preemptions"] == eng.n_preemptions
    assert agg["ttft_s"]["p50"] is not None
    # the trace document is Perfetto-valid with the preempt track populated
    doc = sc.obs.trace.export()
    assert validate_trace(doc) == []
    tracks = {e.get("args", {}).get("name")
              for e in doc["traceEvents"] if e["ph"] == "M"}
    assert "engine.preempt" in tracks and "engine.resume" in tracks
    assert validate_metrics_snapshot(sc.obs.metrics.snapshot()) == []
    snap = sc.obs.metrics.snapshot()
    assert snap["counters"]["engine_preemptions_total"] == eng.n_preemptions


def test_engine_metrics_match_stats(setup):
    """Registry counters must agree with the engine's own stats() ints."""
    cfg, params = setup
    obs = Observability()
    eng = ServingEngine(cfg, params, ServeConfig(
        batch_slots=2, max_len=32, attention=PAGED8, prefix_cache=True,
        obs=obs))
    eng.submit(list(range(1, 10)))
    eng.submit(list(range(1, 10)))      # same prompt → prefix hit
    for _ in range(3):
        eng.step()
    st = eng.stats()
    snap = obs.metrics.snapshot()
    c = snap["counters"]
    assert c["engine_tokens_total{stage=prefill}"] == st["prefill_tokens"]
    assert c["engine_tokens_total{stage=decode}"] == st["decode_tokens"]
    assert c["prefix_hits_total"] == st["prefix_hits"]
    assert c["prefix_hit_tokens_total"] == st["prefix_hit_tokens"]
    assert snap["gauges"]["pool_pages_in_use"] == st["pool_pages_in_use"]
    assert snap["gauges"]["pool_high_water_pages"] == st["pool_high_water"]
    prefills = [e for e in obs.trace.export()["traceEvents"]
                if e["ph"] == "X" and e["name"] == "engine.prefill"]
    assert len(prefills) >= 1
    assert sum(e["args"]["tokens"] for e in prefills) == st["prefill_tokens"]
    assert sum(e["args"]["rows"] for e in prefills) == st["prefill_rows"]
    decodes = [e for e in obs.trace.export()["traceEvents"]
               if e["ph"] == "X" and e["name"] == "engine.decode"]
    assert len(decodes) >= 1
    assert sum(e["args"]["slots"] for e in decodes) == st["decode_tokens"]
    # prefix hit recorded on the request's own trace too
    rts = sorted(eng.request_traces.values(), key=lambda t: t.rid)
    assert rts[1].prefix_hit_tokens > 0


def test_stats_json_roundtrip_both_backends(setup):
    """Satellite: stats() returns plain JSON types on both backends —
    json.dumps round-trips and the key schema is pinned."""
    cfg, params = setup
    core = {"tick", "live_requests", "waiting_requests", "n_preemptions",
            "prefill_tokens", "decode_tokens", "prefill_rows", "decode_rows",
            "engine_s", "engine_wait_s"}
    paged_keys = core | {
        "pool_pages", "pool_free_pages", "pool_pages_in_use",
        "pool_high_water", "kv_dtype", "kv_page_bytes", "kv_pool_bytes",
        "kv_bytes_in_use", "prefix_hits", "prefix_misses",
        "prefix_evictions", "prefix_cow_forks", "prefix_cached_pages",
        "prefix_hit_tokens", "prefix_lookup_tokens", "prefix_hit_rate"}
    for sc, want in ((ServeConfig(batch_slots=2, max_len=32), core),
                     (ServeConfig(batch_slots=2, max_len=32,
                                  attention=PAGED8, prefix_cache=True),
                      paged_keys)):
        eng = ServingEngine(cfg, params, sc)
        eng.submit([1, 2, 3])
        eng.step()
        st = eng.stats()
        assert want <= set(st)
        assert json.loads(json.dumps(st)) == st
        for k, v in st.items():
            assert type(v) in (int, float, str, bool, type(None)), (k, v)


def test_frontend_slo_report(setup):
    import asyncio

    from repro.serving.frontend import AsyncServingEngine
    cfg, params = setup
    eng = ServingEngine(cfg, params, ServeConfig(
        batch_slots=2, max_len=32, attention=PAGED8, obs=Observability()))
    aeng = AsyncServingEngine(eng)

    async def demo():
        return await asyncio.gather(
            aeng.complete([1, 2, 3], 4),
            aeng.complete([4, 5], 4, deadline=0.0))   # already-past deadline

    outs = asyncio.run(demo())
    assert all(len(o) == 4 for o in outs)
    rep = aeng.slo_report()
    assert rep["n_completed"] == 2
    assert rep["n_first_tokens"] == 2
    assert rep["ttft_s"]["p50"] is not None
    assert rep["itl_s"]["p95"] is not None
    assert rep["deadline_misses"] == 1
    assert json.loads(json.dumps(rep)) == rep


def test_instrumented_engine_trace_lint_clean(setup):
    """All telemetry must stay host-side of the jit boundary: the jaxpr
    lint over the instrumented engine's prefill/decode finds nothing."""
    from repro.analysis.trace_lint import lint_engine
    cfg, params = setup
    eng = ServingEngine(cfg, params, ServeConfig(
        batch_slots=2, max_len=32, obs=Observability()))
    assert lint_engine(eng) == []


def test_cancel_live_slot_emits_cancelled_end(setup):
    """Satellite bugfix: cancel() of a LIVE request must close its async
    span with {"cancelled": true} and move the cancelled counter, not the
    retired one — previously only the wait-queue branch did, so a live
    cancel was indistinguishable from a natural completion in traces and
    slo_report()."""
    cfg, params = setup
    for sc in (ServeConfig(batch_slots=2, max_len=32,
                           obs=Observability()),
               ServeConfig(batch_slots=2, max_len=32, attention=PAGED8,
                           obs=Observability())):
        eng = ServingEngine(cfg, params, sc)
        h = eng.submit([1, 2, 3])
        eng.step()
        eng.step()
        assert eng.cancel(h) is True
        c = sc.obs.metrics.snapshot()["counters"]
        assert c["engine_cancelled_total"] == 1
        assert c.get("engine_retired_total", 0) == 0
        ends = [e for e in sc.obs.trace.export()["traceEvents"]
                if e["ph"] == "e" and e["id"] == str(h)]
        assert len(ends) == 1
        assert ends[0]["args"]["cancelled"] is True
        assert ends[0]["args"]["n_tokens"] == 2


def test_cancel_waiting_request_still_counts_cancelled(setup):
    """The wait-queue cancel branch moves the same counter as the
    live-slot branch — one counter, both abort paths."""
    cfg, params = setup
    obs = Observability()
    eng = ServingEngine(cfg, params, ServeConfig(
        batch_slots=2, max_len=16, attention=PAGED8, cache_pages=2,
        obs=obs))
    r0 = eng.submit([1, 2, 3])
    assert r0 is not None
    for _ in range(12):                   # decode until r0 gets preempted
        eng.step()
        if any(w.rid == r0 for w in eng.wait):
            break
    # force the queue case if pressure alone didn't park it
    if not any(w.rid == r0 for w in eng.wait):
        s = next(s for s in range(2) if eng.slot_live[s]
                 and int(eng.slot_rid[s]) == r0)
        eng._preempt(s)
    assert eng.cancel(r0) is True
    c = obs.metrics.snapshot()["counters"]
    assert c["engine_cancelled_total"] == 1
    assert c.get("engine_retired_total", 0) == 0


def test_spec_metrics_and_phase_spans(setup):
    """Speculative decoding telemetry: accepted/rejected counters match
    stats(), the acceptance histogram fills, and draft/verify spans land
    on their own phase tracks."""
    from repro.serving.spec_decode import NGramDrafter
    cfg, params = setup
    obs = Observability()
    eng = ServingEngine(cfg, params, ServeConfig(
        batch_slots=2, max_len=48, attention=PAGED8,
        spec=NGramDrafter(k=4), obs=obs))
    eng.submit([7, 7, 7, 7, 7, 7])
    eng.submit([1, 2, 3, 1, 2, 3])
    for _ in range(10):
        eng.step()
    st = eng.stats()
    assert st["spec_accepted_tokens"] + st["spec_rejected_tokens"] > 0
    snap = obs.metrics.snapshot()
    c = snap["counters"]
    assert c["spec_tokens_total{verdict=accepted}"] == \
        st["spec_accepted_tokens"]
    assert c["spec_tokens_total{verdict=rejected}"] == \
        st["spec_rejected_tokens"]
    assert c.get("spec_rollback_pages_total", 0) == \
        st["spec_rollback_pages"]
    assert snap["histograms"]["spec_acceptance_rate"]["count"] >= 1
    doc = obs.trace.export()
    assert validate_trace(doc) == []
    tracks = {e["args"]["name"] for e in doc["traceEvents"]
              if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"engine.draft", "engine.verify"} <= tracks
    spans = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert {"engine.draft", "engine.verify", "engine.wait"} <= spans


# -- spans on the profiler's clock, always-on counters ----------------------

def profiled_host_events(tmp_path, fn):
    """Run ``fn()`` under a ``jax.profiler`` session; return the events of
    the ``/host:CPU`` plane of the ``.xplane.pb`` it wrote, as
    ``(name, start_ns, end_ns, stats)``."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # annotations only
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    out = []
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                            {k: v for k, v in e.stats}))
    return out


def test_engine_spans_on_profiler_host_plane(setup, tmp_path):
    """With the default NULL_OBS, a few steps of a paged engine under a
    profiler session put engine.step and its children on the host plane,
    with their args, each child inside an engine.step."""
    cfg, params = setup
    B = 2
    eng = ServingEngine(cfg, params, ServeConfig(
        batch_slots=B, max_len=32, attention=PAGED8,
        scheduler=Scheduler(prefill_chunk=4)))

    rids = []

    def serve():
        rids.append(eng.submit([1, 2, 3, 4, 5, 6]))
        for _ in range(3):
            eng.step()
        eng.cancel(rids[-1])

    serve()                               # compile outside the session
    events = profiled_host_events(tmp_path, serve)
    by = {}
    for ev in events:
        by.setdefault(ev[0], []).append(ev)
    for name in ("engine.step", "engine.schedule", "engine.prefill",
                 "engine.decode", "engine.wait", "engine.emit",
                 "engine.admit", "engine.cancel"):
        assert name in by, (name, sorted(by))
    assert len(by["engine.step"]) == 3
    # the first chunk (4 tokens, Sb 4) runs in submit, the rest (2, Sb 2)
    # in the first step
    pf = sorted(by["engine.prefill"], key=lambda e: e[1])
    assert [(e[3]["rid"], e[3]["start"], e[3]["tokens"], e[3]["rows"])
            for e in pf] == [(rids[-1], 0, 4, B * 4), (rids[-1], 4, 2, B * 2)]
    assert all(e[3]["slots"] == 1 for e in by["engine.decode"])
    assert {e[3]["what"] for e in by["engine.wait"]} == \
        {"caches", "first_token", "decode_ids"}
    # the block on a call still writing the caches (the chunk submit()
    # left running) is a wait inside each prefill's dispatch
    caches = sorted((e for e in by["engine.wait"]
                     if e[3]["what"] == "caches"), key=lambda e: e[1])
    assert len(caches) == len(pf)
    assert all(p[1] <= w[1] and w[2] <= p[2] for p, w in zip(pf, caches))
    admit, = by["engine.admit"]
    assert admit[1] <= pf[0][1] and pf[0][2] <= admit[2]
    steps = by["engine.step"]

    def inside_a_step(ev):
        return any(s[1] <= ev[1] and ev[2] <= s[2] for s in steps)

    for name in ("engine.schedule", "engine.decode", "engine.emit"):
        assert all(inside_a_step(e) for e in by[name]), name
    assert inside_a_step(pf[1])
    waits = [e for e in by["engine.wait"] if e[3]["what"] == "decode_ids"]
    assert len(waits) == 3 and all(inside_a_step(e) for e in waits)


def test_row_counters_match_hand_arithmetic(setup):
    """prefill_rows counts B * Sb per prefill call (Sb the power-of-two
    bucket), decode_rows B per decode call, beside the real tokens."""
    cfg, params = setup
    B = 2
    eng = ServingEngine(cfg, params, ServeConfig(
        batch_slots=B, max_len=32, attention=PAGED8,
        scheduler=Scheduler(prefill_chunk=4)))
    eng.submit([1, 2, 3, 4, 5, 6])        # chunk of 4 tokens: Sb 4
    st = eng.stats()
    assert (st["prefill_tokens"], st["prefill_rows"]) == (4, B * 4)
    assert (st["decode_tokens"], st["decode_rows"]) == (0, 0)
    for _ in range(3):                    # chunk of 2 (Sb 2), 3 decodes
        eng.step()
    st = eng.stats()
    assert (st["prefill_tokens"], st["prefill_rows"]) == (6, B * 4 + B * 2)
    assert (st["decode_tokens"], st["decode_rows"]) == (3, 3 * B)
    eng.submit(list(range(1, 6)))         # 5 tokens in one chunk of 4 + 1
    st = eng.stats()
    assert st["prefill_rows"] == B * 4 + B * 2 + B * 4


def test_engine_wait_within_engine_s(setup):
    """engine_wait_s is the part of engine_s spent blocked on the device;
    both only grow."""
    cfg, params = setup
    eng = ServingEngine(cfg, params, ServeConfig(
        batch_slots=2, max_len=32, attention=PAGED8))
    st0 = eng.stats()
    assert st0["engine_s"] == st0["engine_wait_s"] == 0.0
    h = eng.submit([1, 2, 3])
    st1 = eng.stats()
    for _ in range(3):
        eng.step()
    eng.cancel(h)
    st2 = eng.stats()
    for st in (st1, st2):
        assert 0.0 < st["engine_wait_s"] <= st["engine_s"]
    assert st2["engine_s"] > st1["engine_s"]
    assert st2["engine_wait_s"] > st1["engine_wait_s"]


def test_draft_model_waits_count_as_engine_waits(setup):
    """A model drafter's own engine blocks on the device inside the
    target's engine.draft: those waits are the target's waits too."""
    from repro.serving.spec_decode import DraftModelDrafter
    cfg, params = setup
    drafter = DraftModelDrafter(cfg, params, k=2, max_len=32,
                                attention=PAGED8)
    eng = ServingEngine(cfg, params, ServeConfig(
        batch_slots=2, max_len=24, attention=PAGED8, spec=drafter))
    eng.submit([1, 2, 3])
    eng.submit([4, 5, 6, 7])
    for _ in range(3):
        eng.step()
    st = eng.stats()
    assert drafter.wait_s > 0
    assert drafter.wait_s < st["engine_wait_s"] <= st["engine_s"]


def test_gc_span_under_profiler(tmp_path):
    """A forced collection under a profiler session leaves a ``gc`` span
    on the host plane; installing twice hooks once."""
    install_gc_span()
    install_gc_span()
    hooks = [cb for cb in gc.callbacks
             if getattr(cb, "__module__", "") == "repro.obs.tracing"]
    assert len(hooks) == 1
    events = profiled_host_events(tmp_path, lambda: gc.collect())
    gcs = [e for e in events if e[0] == "gc"]
    assert gcs and any(e[3].get("generation") == 2 for e in gcs)
    assert all(e[2] >= e[1] for e in gcs)
