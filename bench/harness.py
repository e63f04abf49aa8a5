"""One run of one cell: set-up, the measured window, the comparison with
the reference, the result line.

Everything that belongs to a configuration, a traffic mix or a per-layer
metric is found by its name in ``BENCHMARK.json``:

- ``bench/configs/<config>.json``: the model and its deployment, on the
  chips its ``serving`` group names, which the cell's ``chips`` must equal;
- ``bench/families/<model_type>.py``: what the configuration's model type
  fixes for the program (``bench/model.py``), and any per-layer work that
  ``bench/flops.py`` counts otherwise than for a dense decoder;
- ``bench/traffic/<traffic>.json``: the mix, read by ``bench/workload.py``;
- ``bench/metrics/<metric>.py``: a reader with ``read(run)`` that returns
  the metric's value, or None when the run has nothing to read;
- ``bench/reference/<model_type>.py``: the plain reference.

So a cell or a metric is added with files and entries, not edits.
"""
from __future__ import annotations

import gc
import json
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from bench import check as C
from bench import trace as TR
from bench.client import Clients, percentile
from bench.model import (init_weights, load_config, load_family,
                         load_module, make_engine, program_config,
                         tp_context)
from bench.peaks import peaks_for
from bench.workload import ChatTraffic, load_mix

TRACE_DIR = ".bench_trace"
# how long after the window closes the client waits for the first tokens
# of requests submitted inside it
FIRST_TOKEN_WAIT_S = 60.0


def configure_jax(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    holding every program however short, so that only a cell's first run
    in a checkout compiles."""
    cache = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


def find(entries: List[Dict], name: str, what: str) -> Dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def cell_metrics(bench: Dict, kind: str, cell: str) -> List[Dict]:
    """The cell's metrics of one kind: those that list it, or list none."""
    return [m for m in bench[kind]
            if cell in m.get("workloads", [cell])]


@dataclass
class WorkLog:
    """What each engine call computed: positions of every prefill and
    decode call (traced runs only; reading them costs a host copy)."""
    calls: List[tuple] = field(default_factory=list)   # (t, kind, pos)

    def wrap(self, eng) -> None:
        prefill, decode = eng.prefill, eng.decode

        def logged_prefill(params, batch, caches):
            self.calls.append((time.perf_counter(), "prefill",
                               np.asarray(batch["positions"])))
            return prefill(params, batch, caches)

        def logged_decode(params, tokens, positions, caches, bt=None):
            self.calls.append((time.perf_counter(), "decode",
                               np.asarray(positions)))
            return decode(params, tokens, positions, caches, bt)

        eng.prefill, eng.decode = logged_prefill, logged_decode

    def between(self, t0: float, t1: float) -> List[tuple]:
        return [c for c in self.calls if t0 <= c[0] < t1]


@dataclass
class Run:
    """What a per-layer metric reader may read."""
    raw: Dict
    family: object                # bench/families/<model_type>.py
    chips: int
    peaks: object
    window: tuple                 # host clock (start, end)
    clients: Clients
    stats0: Dict
    stats1: Dict
    work: List[tuple]             # WorkLog calls inside the traced window
    reduction: Optional[TR.Reduction]


def _load_reader(root: Path, name: str) -> Callable:
    return load_module(root / "bench" / "metrics" / f"{name}.py").read


class GCPauses:
    """Python's garbage collections while it is installed: how many, their
    seconds in all, and the longest."""

    def __init__(self):
        self.n, self.seconds, self.longest, self._t0 = 0, 0.0, 0.0, None
        gc.callbacks.append(self._on)

    def _on(self, phase: str, _info) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            dt = time.perf_counter() - self._t0
            self.n, self.seconds = self.n + 1, self.seconds + dt
            self.longest = max(self.longest, dt)

    def remove(self) -> list:
        gc.callbacks.remove(self._on)
        return [self.n, self.seconds, self.longest]


class CompileCounter:
    def __init__(self):
        self.n, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, seconds: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += seconds


def warm_up(eng, chunk: int) -> None:
    """Compile every program the window drives: the bucketed prefill at
    each power-of-two chunk length up to ``chunk``, and decode. Every row
    is masked (position -1), so nothing is written. Each call is waited
    for before the next: every call returns a new copy of the KV pool, and
    calls queued together would hold several at once."""
    B = eng.sc.batch_slots
    bt = eng._bt_device()
    sb = 1
    while sb <= chunk:
        batch = {"tokens": eng._dev(np.zeros((B, sb), np.int32)),
                 "positions": eng._dev(np.full((B, sb), -1, np.int32)),
                 "last_cols": eng._dev(np.zeros((B,), np.int32)),
                 "block_tables": bt}
        jax.block_until_ready(eng.prefill(eng.params, batch, eng.caches))
        sb *= 2
    masked = eng._dev(np.full((B, 1), -1, np.int32))
    jax.block_until_ready(eng.decode(eng.params, masked, masked, eng.caches,
                                     bt))


def prime_system_prompts(eng, traffic: ChatTraffic) -> None:
    """Serve each system prompt once, so the prefix cache holds them as in
    a deployment that has been running."""
    rids = [eng.submit(p) for p in traffic.system_prompts]
    pending = set(r for r in rids if r is not None)
    while pending:
        for rid in eng.step():
            if rid in pending:
                eng.cancel(rid)
                pending.discard(rid)


def peak_bytes(devices) -> List[Optional[int]]:
    """Each chip's peak bytes in use, where the backend reports it."""
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


def window_metrics(clients: Clients, ws: float, we: float
                   ) -> Dict[str, float]:
    tokens = sum(1 for r in clients.all for t in r.times if ws <= t < we)
    itl = [b - a for r in clients.all for a, b in zip(r.times, r.times[1:])
           if ws <= a and b < we]
    return {"tokens_per_s": tokens / (we - ws),
            "itl_p90_ms": 1e3 * percentile(itl, 90) if itl else None,
            "_itl_n": len(itl), "_tokens": tokens}


def step_times(clients: Clients, ws: float, we: float) -> Dict[str, object]:
    """Host time of the window's steps, apart for steps that ran a prefill
    chunk and steps that only decoded: count, median and widest, in ms."""
    out = {}
    for kind, pick in (("prefill", lambda n: n > 0),
                       ("decode", lambda n: n == 0)):
        ms = [1e3 * s for s, at, n in zip(clients.step_s, clients.step_at,
                                           clients.step_prefill)
              if ws <= at <= we and pick(n)]
        out[kind] = [len(ms), percentile(ms, 50), max(ms, default=None)]
    return out


def contexts(clients: Clients) -> list:
    """Context lengths (prompt and tokens served so far) of the requests in
    flight: least, median, largest."""
    ctx = sorted(len(r.prompt) + len(r.tokens) for r in clients.live.values())
    return [ctx[0], ctx[len(ctx) // 2], ctx[-1]] if ctx else []


def run_cell(root: Path, bench: Dict, workload: str, seed: int,
             seconds: float, trace: bool, t_start: float, *,
             engine_hook: Optional[Callable] = None,
             devices=None, peaks=None, lower: Optional[Dict] = None) -> Dict:
    """Run one cell; returns the result (the last stdout line's object)
    and, under ``"stderr"``, the lines to print last on standard error.
    ``engine_hook(eng)`` plants a fault under the timed path and ``lower``
    switches on a lower-precision path of the program: the check's faults
    and control (``bench/faults.py``, ``bench/calibrate.py``)."""
    cell = find(bench["workloads"], workload, "workload")
    cfg_entry = find(bench["configs"], cell["config"], "config")
    raw = load_config(root, cfg_entry)
    chips = cell["chips"]
    if chips != raw["serving"]["chips"]:
        raise ValueError(
            f"cell {workload!r} asks for {chips} chip(s), but its "
            f"configuration {cfg_entry['file']} is deployed on "
            f"{raw['serving']['chips']}: the two must agree")
    family = load_family(root, raw)
    cfg = program_config(raw, family)
    mix = load_mix(root, cell["traffic"])
    devices = devices if devices is not None else jax.devices()[:chips]
    if len(devices) != chips:
        raise ValueError(f"cell {workload!r} needs {chips} devices, "
                         f"given {len(devices)}")
    tp = tp_context(cfg, devices)
    kind = devices[0].device_kind
    peaks = peaks if peaks is not None else peaks_for(kind)
    srv = raw["serving"]
    compiles = CompileCounter()
    phases = {"start": time.perf_counter() - t_start}

    def phase(name):
        phases[name] = time.perf_counter() - t_start

    eng = make_engine(raw, cfg, family, seed, tp, **(lower or {}))
    phase("engine")
    if engine_hook is not None:
        engine_hook(eng)
    work = WorkLog()
    if trace:
        work.wrap(eng)
    warm_up(eng, srv["prefill_chunk"])
    phase("warm_up")
    traffic = ChatTraffic(mix, seed, cfg.vocab, srv["max_len"])
    prime_system_prompts(eng, traffic)
    phase("prime")
    spans = jax.profiler.TraceAnnotation if trace else None
    clients = Clients(eng, traffic, srv["batch_slots"], spans=spans)
    clients.submit_idle()
    phase("first_wave")
    clients.run_steps(mix["ramp_steps"])
    jax.block_until_ready(eng.caches)
    phase("ramp")

    trace_dir = root / TRACE_DIR / workload
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # spans only, no per-call events
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    n_compiles0 = compiles.n
    stats0 = eng.stats()
    ctx0 = contexts(clients)
    # set-up's objects (compiled programs, traffic, the engine) live for the
    # whole run: keep the window's garbage collections from rescanning them
    gc.collect()
    gc.freeze()
    pauses = GCPauses()
    ws = time.perf_counter()
    setup_s = ws - t_start
    if trace:
        with jax.profiler.TraceAnnotation("window"):
            clients.run_until(ws + seconds)
            we = time.perf_counter()
            jax.block_until_ready(eng.caches)
    else:
        clients.run_until(ws + seconds)
        we = time.perf_counter()
    gc_window = pauses.remove()
    gc.unfreeze()
    stats1 = eng.stats()
    ctx1 = contexts(clients)
    window_compiles = compiles.n - n_compiles0
    if trace:
        jax.profiler.stop_trace()

    # requests submitted in the window get their first token, late or not
    clients.submit_open = False
    deadline = time.perf_counter() + FIRST_TOKEN_WAIT_S
    late = [r for r in clients.all if ws <= r.submitted < we and not r.times]
    while any(not r.times for r in late) and time.perf_counter() < deadline:
        clients.step()
    attempted = sum(1 for r in clients.all if ws <= r.submitted < we)
    failed = sum(1 for r in late if not r.times)

    e2e = window_metrics(clients, ws, we)
    e2e["setup_s"] = setup_s
    mem = peak_bytes(devices)
    known = [p for p in mem if p is not None]
    # served tokens to compare: requests the window finished, and those it
    # left running, with what they were served
    finished = [r for r in clients.finished if ws <= r.done < we]
    running = [r for r in clients.live.values() if r.tokens]
    del eng, clients.eng
    gc.collect()

    # the comparison with the reference, once the program's state is freed;
    # the weights are made again from the seed, as the program got them and
    # in its placement
    t_ref = time.perf_counter()
    weights = init_weights(cfg, family, seed, tp)
    sample = C.sample(finished, running, seed, srv["check_min_tokens"],
                      srv["check_max_requests"])
    ref = C.Reference(root, raw, srv["max_len"],
                      mix["answer_tokens"]["max"])
    read = C.readings(ref, weights, sample)
    verdict = C.judge(read, raw["correct"])
    phases["reference_s"] = time.perf_counter() - t_ref
    if not sample:
        verdict["correct"] = False

    result = {"correct": verdict["correct"] and failed == 0,
              "attempted": attempted, "failed": failed}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": max(known) if known else None}
    if trace:
        run = _traced(root, bench, workload, raw, family, chips, peaks,
                      trace_dir, (ws, we), clients, stats0, stats1, work)
        result["metrics"], device["busy_s"], device["window_s"] = run[:3]
        result["breakdown"] = run[3]
    else:
        result["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in cell_metrics(bench, "end_to_end", workload)
            if e2e.get(m["name"]) is not None}
    result["device"] = device
    result["check"] = verdict["check"]
    info = {"window_s": we - ws, "tokens": e2e["_tokens"],
            "itl_samples": e2e["_itl_n"],
            "finished_in_window": len(finished),
            "readings": {k: v for k, v in read.items() if k != "tokens"},
            "compared_tokens": read["tokens"],
            "compared_requests": len(sample),
            "compiles_in_window": window_compiles,
            "memory_peak_bytes_per_chip": mem,
            "compile_s_total": compiles.seconds,
            "preemptions": stats1["n_preemptions"] - stats0["n_preemptions"],
            # pages of the pool in use and the contexts in flight (least,
            # median, largest) as the window opens and as it closes
            "pool_pages": stats1.get("pool_pages"),
            "pages_in_use": [stats0.get("pool_pages_in_use"),
                             stats1.get("pool_pages_in_use")],
            "contexts": [ctx0, ctx1],
            # [count, median, widest] host ms of the window's steps
            "step_ms": step_times(clients, ws, we),
            # garbage collections in the window: [count, seconds, longest]
            "gc_in_window": gc_window,
            "setup_phases_s": phases,
            "e2e": {k: v for k, v in e2e.items() if not k.startswith("_")}}
    result["stderr"] = ([f"info {json.dumps(info)}"]
                        + [f"check {k} {v['value']!r} limit {v['limit']!r}"
                           for k, v in verdict["check"].items()])
    return result


def _traced(root, bench, workload, raw, family, chips, peaks, trace_dir,
            window, clients, stats0, stats1, work):
    files = sorted(trace_dir.glob("**/*.xplane.pb"))
    if not files:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    events = TR.load_xplane(files[-1], keep=TR.relevant)
    win = [e for e in events if e.plane == TR.HOST_PLANE
           and e.name == "window"]
    if not win:
        raise RuntimeError("the trace holds no 'window' span")
    t0, t1 = win[0].start_ns, win[0].end_ns
    red = TR.reduce(events, t0, t1)
    ws, we = window
    run = Run(raw, family, chips, peaks, window, clients, stats0, stats1,
              work.between(ws, we), red)
    metrics = {}
    for m in cell_metrics(bench, "per_layer", workload):
        value = _load_reader(root, m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    breakdown = {"device_ops": [[n, s] for n, s in red.top_ops(10)],
                 "idle_gaps": [[n, s] for n, s in red.gaps[:10]]}
    return metrics, red.mean_busy_s(), red.window_s, breakdown


def print_result(result: Dict) -> None:
    lines = result.pop("stderr")
    for ln in lines:
        print(ln, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
