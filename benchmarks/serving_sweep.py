"""Serving sweep: paged vs contiguous KV cache under a skewed-length mix.

The workload is the shape the paged subsystem exists for: 90 % short
prompts, 10 % near-``max_len`` prompts (the "millions of users, wildly
mixed lengths" regime in ROADMAP.md). The contiguous engine reserves
``batch_slots × max_len`` KV rows no matter what arrives; the paged engine
(docs/serving.md) backs only resident tokens, so the same pool serves a
request set whose summed max_len-padded footprint *exceeds* the pool — the
capacity acceptance gate (asserted hard in tests/test_serving.py, reported
here as the ``oversubscription`` column).

Reported per engine: tokens/s, peak cache bytes actually backing tokens,
**per-shard** peak cache bytes (the resident KV footprint each model shard
holds — pool tensors split on the KV-head dim under TP, docs/serving.md),
peak concurrently-live requests, preemptions, and oversubscription =
(peak live requests × max_len-padded bytes) / cache budget. On CPU the
paged kernel runs in Pallas *interpret* mode — a correctness substrate, not
a speed one — so tokens/s only becomes a fair fight on TPU (backend
"paged" vs "fused"); the memory columns are platform-independent.

``--tp N`` adds a ``paged_tpN`` cell: the same paged engine sharded over a
(data, model) host mesh with an N-way model axis (ServeConfig.mesh,
repro/distributed/tp.py). Needs ``len(jax.devices())`` divisible by N —
force host devices via XLA_FLAGS=--xla_force_host_platform_device_count.

``--kv-suite`` runs the quantized-KV capacity cells instead (``sweep_kv``):
the same one-page-per-request mix served from a bf16 pool and from an int8
pool (``ServeConfig(kv_dtype="int8")``, docs/quant.md#kv-pages) holding at
most the same pool *bytes* — the gate is ≥1.8× peak resident requests
under int8.

``--spec-suite`` runs the speculative-decoding cells instead
(``sweep_spec``): plain greedy vs n-gram self-speculation
(``ServeConfig(spec=NGramDrafter(k))``, docs/serving.md
#speculative-decoding) over one request set, asserting the streams are
token-identical and the spec cell clears ≥1.5× tokens/s.

Rows go to the shared CSV (benchmarks/common.py) and, matching
benchmarks/hillclimb.py, to ``serving_sweep.jsonl`` (``serving_kv.jsonl``
/ ``serving_spec.jsonl`` for the kv / spec suites).

  python -m benchmarks.serving_sweep
  python -m benchmarks.serving_sweep --max-len 128 --n-requests 24 \
      --cache-pages-frac 0.5
  XLA_FLAGS=--xla_force_host_platform_device_count=2 \
      python -m benchmarks.serving_sweep --tp 2
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import List, Optional, Sequence

import jax
import numpy as np

from benchmarks.common import emit
from repro.configs.registry import get_smoke_config
from repro.core.plan import AttentionPolicy
from repro.models import transformer as T
from repro.serving.engine import ServeConfig, ServingEngine
from repro.serving.scheduler import Scheduler
from repro.serving.spec_decode import NGramDrafter


def skewed_prompts(rng, n: int, max_len: int, short_frac: float = 0.9
                   ) -> List[List[int]]:
    """90 % short (2–6 tokens), 10 % near-max_len (~3/4 of it)."""
    prompts = []
    for i in range(n):
        if rng.random() < short_frac:
            L = int(rng.integers(2, 7))
        else:
            L = max(2, (3 * max_len) // 4)
        prompts.append(rng.integers(0, 64, L).tolist())
    return prompts


def shared_prefix_prompts(rng, n: int, prefix_len: int, tail_lo: int = 2,
                          tail_hi: int = 8) -> List[List[int]]:
    """The system-prompt traffic shape (docs/serving.md#prefix-cache):
    every request opens with the same ``prefix_len`` tokens and appends a
    short random tail — the mix the prefix cache turns from O(prompt) into
    O(tail) prefill work and from private to shared pages."""
    shared = rng.integers(0, 64, prefix_len).tolist()
    return [shared
            + rng.integers(0, 64,
                           int(rng.integers(tail_lo, tail_hi + 1))).tolist()
            for _ in range(n)]


def poisson_arrival_steps(rng, n: int, rate: float) -> List[int]:
    """Bursty arrivals: request i becomes eligible at engine step
    ``steps[i]`` (cumulative exponential inter-arrival gaps at ``rate``
    requests per step — the Poisson process, measured in steps so the
    trace is platform-independent)."""
    gaps = rng.exponential(1.0 / rate, n)
    return np.floor(np.cumsum(gaps)).astype(int).tolist()


def kv_bytes_per_token(cfg, cache_dtype: str = "bfloat16") -> int:
    """K + V payload bytes per cached token per layer stack at
    ``cache_dtype``. Paged cells don't use this estimate: serve_workload
    reads the engine's own exact per-page bytes (engine.kv_page_bytes()),
    which also folds in the int8 pools' fp32 scale side-tensors."""
    elem = jax.numpy.dtype(cache_dtype).itemsize
    return 2 * cfg.n_kv_heads * cfg.head_dim * elem * cfg.n_layers


def serve_workload(cfg, params, sc: ServeConfig, prompts: List[List[int]],
                   gen_len: int, axes=None,
                   arrival_steps: Optional[List[int]] = None):
    """Serve every prompt for gen_len tokens via submit()/step(); returns
    measured stats. Peak memory is sampled after every step.

    ``arrival_steps`` makes the trace bursty: request i only becomes
    eligible for submission at that engine step (None → everything arrives
    up front). TTFT is wall-clock from a request's eligibility to its
    first reported token — queueing delay included, which is exactly what
    admission capacity (prefix sharing) and chunked prefill move."""
    eng = ServingEngine(cfg, params, sc, axes=axes)
    # paged: exact bytes from the engine (int8 payload + scale tensors
    # included); contiguous: the analytic cache_dtype estimate
    per_tok = (eng.kv_page_bytes() // eng.pool.page_size if eng.paged
               else kv_bytes_per_token(cfg, sc.cache_dtype))
    n = len(prompts)
    arrivals = (list(arrival_steps) if arrival_steps is not None
                else [0] * n)
    queue = sorted(range(n), key=lambda i: arrivals[i])
    done: dict = {}
    live_handles: dict = {}
    arrive_t: dict = {}
    ttft: dict = {}
    last_t: dict = {}
    itls: List[float] = []
    total_done = 0
    n_finished = 0
    peak_live = 0
    peak_resident = 0
    peak_tokens = 0
    n_steps = 0
    t0 = time.perf_counter()
    while queue or live_handles:
        while queue and arrivals[queue[0]] <= n_steps:
            i = queue[0]
            arrive_t.setdefault(i, time.perf_counter())
            h = eng.submit(prompts[i])
            if h is None:
                break
            live_handles[h] = i
            queue.pop(0)
        stepped = eng.step()
        n_steps += 1
        now = time.perf_counter()
        for h, t in stepped.items():
            if h not in live_handles:
                continue
            i = live_handles[h]
            done[h] = done.get(h, 0) + 1
            if done[h] == 1:
                ttft[i] = now - arrive_t[i]
            else:
                itls.append(now - last_t[i])   # inter-token latency
            last_t[i] = now
            if done[h] >= gen_len:
                eng.cancel(h)
                del live_handles[h]
                last_t.pop(i, None)
                total_done += done.pop(h)   # contiguous handles (slot ids)
                n_finished += 1             # recycle — don't inherit counts
        # paged: waiting requests are parked host-side, resident = pool use
        n_live = len(live_handles)
        peak_live = max(peak_live, n_live)
        # resident = requests actually occupying device slots right now
        # (admitted and not preempted) — the capacity a pool byte buys
        peak_resident = max(peak_resident, int(eng.slot_live.sum()))
        if eng.paged:
            resident = eng.pool.pages_in_use * eng.pool.page_size
        else:
            resident = eng.sc.batch_slots * eng.sc.max_len
        peak_tokens = max(peak_tokens, resident)
        if n_steps > 10000:  # safety valve
            break
    dt = time.perf_counter() - t0
    total = total_done + sum(done.values())
    budget_tokens = (eng.pool.n_pages * eng.pool.page_size if eng.paged
                     else eng.sc.batch_slots * eng.sc.max_len)
    kv_shards = eng.kv_shards()

    def pct(xs, q):
        return round(float(np.percentile(xs, q)), 4) if xs else 0.0
    waits = sorted(ttft.values())
    return {
        "tokens": total,
        "finished": n_finished,
        "tok_per_s": total / max(dt, 1e-9),
        "ttft_p50_s": pct(waits, 50),
        "ttft_p95_s": pct(waits, 95),
        "ttft_p99_s": pct(waits, 99),
        "itl_p50_s": pct(itls, 50),
        "itl_p95_s": pct(itls, 95),
        "itl_p99_s": pct(itls, 99),
        "peak_cache_bytes": peak_tokens * per_tok,
        # what each model shard actually holds resident: the pool splits
        # on the KV-head dim, the page *count* is identical per shard
        "kv_shards": kv_shards,
        "per_shard_peak_cache_bytes": peak_tokens * per_tok // kv_shards,
        "budget_cache_bytes": budget_tokens * per_tok,
        "padded_peak_bytes": peak_live * sc.max_len * per_tok,
        "oversubscription": (peak_live * sc.max_len) / budget_tokens,
        "peak_live_requests": peak_live,
        "peak_resident_requests": peak_resident,
        "preemptions": eng.n_preemptions if eng.paged else 0,
        "steps": n_steps,
        # the engine's own observability dict: prefill/decode token split,
        # pool high-water mark, prefix hit/miss/evict counters + hit rate
        **{k: v for k, v in eng.stats().items()
           if k not in ("tick", "live_requests", "waiting_requests")},
    }


def sweep(arch: str = "smollm-135m", n_layers: int = 2, max_len: int = 64,
          batch_slots: int = 4, n_requests: int = 12, gen_len: int = 8,
          page_size: int = 8, cache_pages_frac: float = 0.5,
          seed: int = 0, jsonl_path: Optional[str] = None, tp: int = 1):
    # --tp shards the KV pool only when the smoke config's heads divide the
    # model axis AND the kv_heads rule allows it; clear the per-arch
    # replication overrides so the TP cell measures an actually-split pool.
    cfg_kw = dict(n_layers=n_layers, vocab=64)
    if tp > 1:
        cfg_kw["sharding_overrides"] = ()
    cfg = get_smoke_config(arch, **cfg_kw)
    params, axes = T.init_model(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    prompts = skewed_prompts(rng, n_requests, max_len)

    n_blocks = -(-max_len // page_size)
    cache_pages = max(n_blocks,
                      int(batch_slots * n_blocks * cache_pages_frac))
    paged_attn = AttentionPolicy(backend="paged",
                                 page_size=page_size, block_q=16)
    cells = {
        "contiguous": ServeConfig(
            batch_slots=batch_slots, max_len=max_len,
            attention=AttentionPolicy(backend="unfused")),
        "paged": ServeConfig(
            batch_slots=batch_slots, max_len=max_len, attention=paged_attn,
            cache_pages=cache_pages),
    }
    if tp > 1:
        from repro.launch.mesh import make_host_mesh
        if len(jax.devices()) % tp:
            print(f"[serving] skipping --tp {tp}: {len(jax.devices())} "
                  f"device(s) not divisible (set XLA_FLAGS="
                  f"--xla_force_host_platform_device_count=N)")
        else:
            cells[f"paged_tp{tp}"] = ServeConfig(
                batch_slots=batch_slots, max_len=max_len,
                attention=paged_attn, cache_pages=cache_pages,
                mesh=make_host_mesh(model=tp))
    rows = []
    for name, sc in cells.items():
        stats = serve_workload(cfg, params, sc, prompts, gen_len, axes=axes)
        row = {"engine": name, "arch": cfg.name, "max_len": max_len,
               "batch_slots": batch_slots, "page_size": page_size,
               "cache_pages": cache_pages if name.startswith("paged")
               else None, "tp": tp if name.endswith(f"tp{tp}") else 1,
               **stats}
        rows.append(row)
        emit("serving", f"{name}_tok_per_s", round(stats["tok_per_s"], 2),
             "tok/s", requests=n_requests, gen_len=gen_len)
        emit("serving", f"{name}_peak_cache", stats["peak_cache_bytes"],
             "bytes", budget=stats["budget_cache_bytes"],
             per_shard=stats["per_shard_peak_cache_bytes"],
             kv_shards=stats["kv_shards"],
             oversubscription=round(stats["oversubscription"], 3),
             peak_live=stats["peak_live_requests"],
             preemptions=stats["preemptions"])
    out = jsonl_path or os.path.join(os.path.dirname(__file__),
                                     "serving_sweep.jsonl")
    with open(out, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    print(f"[serving] wrote {len(rows)} rows to {out}")
    paged = next(r for r in rows if r["engine"] == "paged")
    if paged["oversubscription"] > 1.0:
        print(f"[serving] capacity: paged served a live set "
              f"{paged['oversubscription']:.2f}x its cache budget "
              f"(admission is page-bound, not slot-bound)")
    return rows


def sweep_prefix(arch: str = "smollm-135m", n_layers: int = 2,
                 max_len: int = 96, batch_slots: int = 8,
                 n_requests: int = 20, gen_len: int = 3, page_size: int = 8,
                 prefix_len: int = 72, cache_pages: Optional[int] = None,
                 arrival_rate: float = 0.4, seed: int = 0,
                 jsonl_path: Optional[str] = None):
    """Prefix-cache acceptance sweep (ISSUE 6): a shared-prefix mix and a
    bursty (Poisson-arrival) mix, each served by the paged engine with and
    without the prefix cache at a FIXED pool size. Reports tokens/s, TTFT
    p50/p95, peak admitted concurrency, and the prefix hit rate — the
    gates are ≥2× peak concurrent requests and ≥1.5× tokens/s on the
    shared-prefix mix.

    Default shapes are prefill-dominated (long shared prefix, short
    tails and gen_len) on purpose: the paged kernel here runs in Pallas
    *interpret* mode, where decode cost grows with the summed resident
    key blocks of the live set — host-sequential, so the extra
    concurrency the cache unlocks does not amortize decode the way real
    hardware does. Prefill work elided by the cache (O(prompt) →
    O(tail)) is the platform-independent win; decode-heavy mixes need a
    compiled backend for the throughput gate to be a fair fight."""
    cfg = get_smoke_config(arch, n_layers=n_layers, vocab=64)
    params, axes = T.init_model(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    prompts = shared_prefix_prompts(rng, n_requests, prefix_len)
    arrivals = poisson_arrival_steps(rng, n_requests, arrival_rate)

    n_blocks = -(-max_len // page_size)
    # pool sized so an UNSHARED request set is page-starved (~2 concurrent)
    # while shared prefixes fit many: the capacity the cache must unlock
    pages = cache_pages if cache_pages is not None else 2 * n_blocks
    paged_attn = AttentionPolicy(backend="paged",
                                 page_size=page_size, block_q=16)
    base = dict(batch_slots=batch_slots, max_len=max_len,
                attention=paged_attn, cache_pages=pages)
    cells = {
        "nocache": (ServeConfig(**base), None),
        "prefix": (ServeConfig(**base, prefix_cache=True), None),
        "nocache_bursty": (ServeConfig(**base), arrivals),
        "prefix_bursty": (ServeConfig(**base, prefix_cache=True,
                                      scheduler=Scheduler(prefill_chunk=16)),
                          arrivals),
    }
    rows = []
    for name, (sc, arr) in cells.items():
        stats = serve_workload(cfg, params, sc, prompts, gen_len, axes=axes,
                               arrival_steps=arr)
        row = {"engine": name, "arch": cfg.name, "max_len": max_len,
               "batch_slots": batch_slots, "page_size": page_size,
               "cache_pages": pages, "prefix_len": prefix_len,
               "n_requests": n_requests, "gen_len": gen_len,
               "arrival_rate": arrival_rate if arr is not None else None,
               **stats}
        rows.append(row)
        emit("serving-prefix", f"{name}_tok_per_s",
             round(stats["tok_per_s"], 2), "tok/s",
             peak_live=stats["peak_live_requests"],
             ttft_p50_s=stats["ttft_p50_s"],
             ttft_p95_s=stats["ttft_p95_s"],
             hit_rate=stats.get("prefix_hit_rate", 0.0))
    out = jsonl_path or os.path.join(os.path.dirname(__file__),
                                     "serving_prefix.jsonl")
    with open(out, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    print(f"[serving-prefix] wrote {len(rows)} rows to {out}")
    by = {r["engine"]: r for r in rows}
    live_x = (by["prefix"]["peak_live_requests"]
              / max(by["nocache"]["peak_live_requests"], 1))
    tput_x = (by["prefix"]["tok_per_s"]
              / max(by["nocache"]["tok_per_s"], 1e-9))
    print(f"[serving-prefix] shared-prefix mix at {pages} pages: "
          f"{live_x:.2f}x peak concurrent requests "
          f"({by['nocache']['peak_live_requests']} -> "
          f"{by['prefix']['peak_live_requests']}), "
          f"{tput_x:.2f}x tokens/s, hit rate "
          f"{by['prefix'].get('prefix_hit_rate', 0.0):.1%}")
    print(f"[serving-prefix] bursty (Poisson {arrival_rate}/step): TTFT "
          f"p50 {by['nocache_bursty']['ttft_p50_s']:.3f}s -> "
          f"{by['prefix_bursty']['ttft_p50_s']:.3f}s, p95 "
          f"{by['nocache_bursty']['ttft_p95_s']:.3f}s -> "
          f"{by['prefix_bursty']['ttft_p95_s']:.3f}s")
    return rows


def sweep_kv(arch: str = "smollm-135m", n_layers: int = 2,
             max_len: int = 16, batch_slots: int = 16,
             n_requests: int = 24, prompt_len: int = 3, gen_len: int = 4,
             page_size: int = 8, fp_pages: int = 8, seed: int = 0,
             jsonl_path: Optional[str] = None):
    """Quantized-KV capacity sweep (docs/quant.md#kv-pages): the same
    request mix served by the paged engine with a model-dtype (bf16) pool
    and with an int8 pool holding AT MOST the same pool **bytes** — int8
    pages = floor(byte budget / int8 page bytes), where an int8 page costs
    half the payload plus two (P, Hkv) fp32 scale rows per layer.

    Requests are sized to live inside exactly one page (prompt_len +
    gen_len + 1 <= page_size, counting the pending-token write), so peak
    resident concurrency == pages the pool can hold — the cleanest
    possible read of "live requests per pool byte". Gate (asserted in
    tests/test_serving.py, printed here): >=1.8x peak resident requests
    under int8 (2x payload minus the scale side-tensors' overhead)."""
    assert prompt_len + gen_len + 1 <= page_size, "requests must fit 1 page"
    cfg = get_smoke_config(arch, n_layers=n_layers, vocab=64)
    params, axes = T.init_model(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, 64, prompt_len).tolist()
               for _ in range(n_requests)]

    # equal pool-BYTE budget across the two cells
    fp_page = page_size * kv_bytes_per_token(cfg, "bfloat16")
    int8_page = (page_size * kv_bytes_per_token(cfg, "int8")
                 + 2 * cfg.n_kv_heads * 4 * cfg.n_layers)   # fp32 scales
    budget = fp_pages * fp_page
    int8_pages = budget // int8_page
    paged_attn = AttentionPolicy(backend="paged",
                                 page_size=page_size, block_q=16)
    base = dict(batch_slots=batch_slots, max_len=max_len,
                attention=paged_attn, cache_dtype="bfloat16")
    cells = {
        "kv_bf16": ServeConfig(**base, cache_pages=fp_pages),
        "kv_int8": ServeConfig(**base, cache_pages=int8_pages,
                               kv_dtype="int8"),
    }
    rows = []
    for name, sc in cells.items():
        stats = serve_workload(cfg, params, sc, prompts, gen_len, axes=axes)
        assert stats["kv_pool_bytes"] <= budget, (
            name, stats["kv_pool_bytes"], budget)
        row = {"engine": name, "arch": cfg.name, "max_len": max_len,
               "batch_slots": batch_slots, "page_size": page_size,
               "cache_pages": sc.cache_pages, "n_requests": n_requests,
               "prompt_len": prompt_len, "gen_len": gen_len,
               "budget_pool_bytes": budget, **stats}
        rows.append(row)
        emit("serving-kv", f"{name}_peak_resident",
             stats["peak_resident_requests"], "requests",
             pool_bytes=stats["kv_pool_bytes"], pages=sc.cache_pages,
             tok_per_s=round(stats["tok_per_s"], 2),
             preemptions=stats["preemptions"])
    out = jsonl_path or os.path.join(os.path.dirname(__file__),
                                     "serving_kv.jsonl")
    with open(out, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    print(f"[serving-kv] wrote {len(rows)} rows to {out}")
    by = {r["engine"]: r for r in rows}
    ratio = (by["kv_int8"]["peak_resident_requests"]
             / max(by["kv_bf16"]["peak_resident_requests"], 1))
    print(f"[serving-kv] capacity at a {budget}-byte pool budget: "
          f"{ratio:.2f}x peak resident requests "
          f"({by['kv_bf16']['peak_resident_requests']} -> "
          f"{by['kv_int8']['peak_resident_requests']}; "
          f"{by['kv_bf16']['cache_pages']} bf16 pages @ "
          f"{by['kv_bf16']['kv_page_bytes']} B vs "
          f"{by['kv_int8']['cache_pages']} int8 pages @ "
          f"{by['kv_int8']['kv_page_bytes']} B) "
          f"[gate: >=1.8x]")
    return rows


def _serve_spec_streams(cfg, params, sc: ServeConfig,
                        prompts: List[List[int]], gen_len: int, axes=None):
    """Serve every prompt for exactly ``gen_len`` tokens, collecting the
    FULL per-request stream. A speculative engine's step() returns
    multi-token *bursts* per handle — ``serve_workload``'s one-token-per-
    step accounting would undercount them, and the spec gate needs the
    literal token sequences to prove stream identity anyway. Returns
    ({prompt index: stream}, stats)."""
    eng = ServingEngine(cfg, params, sc, axes=axes)
    streams = {i: [] for i in range(len(prompts))}
    hmap: dict = {}
    queue = list(range(len(prompts)))
    n_steps = 0
    t0 = time.perf_counter()
    while queue or hmap:
        while queue:
            h = eng.submit(list(prompts[queue[0]]))
            if h is None:
                break
            hmap[h] = queue.pop(0)
        stepped = eng.step()
        n_steps += 1
        for h, t in stepped.items():
            i = hmap.get(h)
            if i is None:
                continue
            streams[i].extend(t if isinstance(t, list) else [t])
            if len(streams[i]) >= gen_len:
                eng.cancel(h)
                del hmap[h]
        assert n_steps <= 10_000, "spec workload failed to converge"
    dt = time.perf_counter() - t0
    streams = {i: s[:gen_len] for i, s in streams.items()}
    st = eng.stats()
    total = sum(len(s) for s in streams.values())
    return streams, {
        "tokens": total,
        "steps": n_steps,
        "wall_s": round(dt, 3),
        "tok_per_s": total / max(dt, 1e-9),
        "spec_acceptance_rate": st.get("spec_acceptance_rate"),
        "spec_accepted_tokens": st.get("spec_accepted_tokens", 0),
        "spec_rejected_tokens": st.get("spec_rejected_tokens", 0),
        "spec_rollback_pages": st.get("spec_rollback_pages", 0),
        "rollback_pages_per_s": round(
            st.get("spec_rollback_pages", 0) / max(dt, 1e-9), 3),
    }


def sweep_spec(arch: str = "smollm-135m", n_layers: int = 2,
               max_len: Optional[int] = None, batch_slots: int = 4,
               n_requests: int = 4, gen_len: int = 384, page_size: int = 8,
               draft_k: int = 8, seed: int = 0,
               jsonl_path: Optional[str] = None):
    """Speculative-decoding acceptance sweep (docs/serving.md
    #speculative-decoding): the same greedy request set served by the
    paged engine with and without n-gram self-speculation
    (``ServeConfig(spec=NGramDrafter(k))``). Asserted gates:

    * **stream identity** — every spec stream equals the non-spec stream
      token for token (the tentpole invariant, end to end through the
      benchmark's own submit/step/cancel loop);
    * **>=1.5x tokens/s** for the spec cell on this workload.

    Long greedy generations from the smoke model are eventually periodic
    (tiny vocab + deterministic argmax -> the streams fall into constant
    runs and short cycles), which is exactly the regime prompt-lookup
    drafting exploits: the drafter locks onto the period and the verify
    pass accepts near-full bursts, so one fixed-shape Sq=1+k forward
    replaces up to k+1 sequential decode steps. The ~1.5x+ here is the
    *host-interpret* win (fewer Pallas interpret passes); on real
    hardware the same step reduction applies to the memory-bound decode
    loop. Also reported: acceptance rate and rollback pages/s — the cost
    side of speculation (rejected drafts shedding their tail pages)."""
    cfg = get_smoke_config(arch, n_layers=n_layers, vocab=64)
    params, axes = T.init_model(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, 64, int(rng.integers(6, 13))).tolist()
               for _ in range(n_requests)]
    if max_len is None:
        # headroom for the longest prompt + gen_len with pages to spare
        max_len = gen_len + 16
    paged_attn = AttentionPolicy(backend="paged",
                                 page_size=page_size, block_q=16)
    base = dict(batch_slots=batch_slots, max_len=max_len,
                attention=paged_attn)
    cells = {
        "greedy": ServeConfig(**base),
        "spec_ngram": ServeConfig(**base, spec=NGramDrafter(k=draft_k)),
    }
    rows, streams = [], {}
    for name, sc in cells.items():
        s, stats = _serve_spec_streams(cfg, params, sc, prompts, gen_len,
                                       axes=axes)
        streams[name] = s
        row = {"engine": name, "arch": cfg.name, "max_len": max_len,
               "batch_slots": batch_slots, "page_size": page_size,
               "n_requests": n_requests, "gen_len": gen_len,
               "draft_k": draft_k if name != "greedy" else None, **stats}
        rows.append(row)
        emit("serving-spec", f"{name}_tok_per_s",
             round(stats["tok_per_s"], 2), "tok/s",
             steps=stats["steps"],
             acceptance=stats["spec_acceptance_rate"],
             rollback_pages_per_s=stats["rollback_pages_per_s"])
    for i in range(n_requests):
        assert streams["spec_ngram"][i] == streams["greedy"][i], (
            f"stream identity violated for request {i}: speculative "
            f"greedy decoding must be token-identical to plain greedy")
    out = jsonl_path or os.path.join(os.path.dirname(__file__),
                                     "serving_spec.jsonl")
    with open(out, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    print(f"[serving-spec] wrote {len(rows)} rows to {out}")
    by = {r["engine"]: r for r in rows}
    ratio = (by["spec_ngram"]["tok_per_s"]
             / max(by["greedy"]["tok_per_s"], 1e-9))
    print(f"[serving-spec] identical streams over {n_requests} requests x "
          f"{gen_len} tokens: {ratio:.2f}x tokens/s "
          f"({by['greedy']['tok_per_s']:.1f} -> "
          f"{by['spec_ngram']['tok_per_s']:.1f}; "
          f"{by['greedy']['steps']} -> {by['spec_ngram']['steps']} steps), "
          f"acceptance {by['spec_ngram']['spec_acceptance_rate']:.1%}, "
          f"rollback {by['spec_ngram']['rollback_pages_per_s']:.1f} "
          f"pages/s [gate: >=1.5x]")
    assert ratio >= 1.5, (
        f"speculative decoding gate failed: {ratio:.2f}x tokens/s < 1.5x "
        f"(acceptance {by['spec_ngram']['spec_acceptance_rate']:.1%})")
    return rows


def run():
    """Default suite entry (benchmarks.run): CPU-safe sizes."""
    sweep()


def run_prefix():
    """Prefix-cache suite entry (benchmarks.run serving-prefix): the
    shared-prefix and bursty mixes at CPU-safe sizes."""
    sweep_prefix()


def run_kv():
    """Quantized-KV suite entry (benchmarks.run serving-kv): the
    equal-pool-byte bf16-vs-int8 capacity cells at CPU-safe sizes."""
    sweep_kv()


def run_spec():
    """Speculative-decoding suite entry (benchmarks.run serving-spec):
    the identical-streams throughput gate (>=1.5x tokens/s with n-gram
    self-speculation) at CPU-safe sizes."""
    sweep_spec()


def run_tp():
    """TP suite entry (benchmarks.run serving-tp): adds the paged_tp2 cell
    when the host has the devices for a 2-way model axis; prints a skip on
    the stock 1-device CPU (force devices via XLA_FLAGS to enable)."""
    if len(jax.devices()) < 2:
        print("[serving] serving-tp skipped: 1 local device (set XLA_FLAGS="
              "--xla_force_host_platform_device_count=2 before jax init)")
        return
    sweep(arch="qwen3-8b", tp=2)


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--n-layers", type=int, default=2)
    # shape flags default to None → each suite's own defaults apply
    # (the skewed sweep and the prefix suite tune them differently)
    ap.add_argument("--max-len", type=int, default=None)
    ap.add_argument("--batch-slots", type=int, default=None)
    ap.add_argument("--n-requests", type=int, default=None)
    ap.add_argument("--gen-len", type=int, default=None)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--cache-pages-frac", type=float, default=0.5,
                    help="paged pool size as a fraction of the contiguous-"
                         "equivalent page count")
    ap.add_argument("--tp", type=int, default=1,
                    help="add a paged_tpN cell: the paged engine over a "
                         "(data, model) host mesh with an N-way model axis "
                         "(tokens/s + per-shard peak cache bytes)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prefix-suite", action="store_true",
                    help="run the prefix-cache acceptance sweep (shared-"
                         "prefix + bursty Poisson mixes) instead of the "
                         "paged-vs-contiguous skewed-length sweep")
    ap.add_argument("--prefix-len", type=int, default=None,
                    help="prefix suite: shared tokens heading every prompt")
    ap.add_argument("--kv-suite", action="store_true",
                    help="run the quantized-KV capacity sweep instead: "
                         "bf16 vs int8 KV pages at an equal pool-byte "
                         "budget (docs/quant.md#kv-pages)")
    ap.add_argument("--spec-suite", action="store_true",
                    help="run the speculative-decoding sweep instead: "
                         "greedy vs n-gram self-speculation at identical "
                         "streams (docs/serving.md#speculative-decoding)")
    ap.add_argument("--draft-k", type=int, default=None,
                    help="spec suite: per-step draft budget k")
    args = ap.parse_args(argv)
    shape = {k: v for k, v in (("max_len", args.max_len),
                               ("batch_slots", args.batch_slots),
                               ("n_requests", args.n_requests),
                               ("gen_len", args.gen_len))
             if v is not None}
    if args.prefix_suite:
        if args.prefix_len is not None:
            shape["prefix_len"] = args.prefix_len
        sweep_prefix(arch=args.arch, n_layers=args.n_layers,
                     page_size=args.page_size, seed=args.seed, **shape)
        return 0
    if args.kv_suite:
        sweep_kv(arch=args.arch, n_layers=args.n_layers,
                 page_size=args.page_size, seed=args.seed, **shape)
        return 0
    if args.spec_suite:
        if args.draft_k is not None:
            shape["draft_k"] = args.draft_k
        sweep_spec(arch=args.arch, n_layers=args.n_layers,
                   page_size=args.page_size, seed=args.seed, **shape)
        return 0
    sweep(arch=args.arch, n_layers=args.n_layers, page_size=args.page_size,
          cache_pages_frac=args.cache_pages_frac, seed=args.seed,
          tp=args.tp, **shape)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
