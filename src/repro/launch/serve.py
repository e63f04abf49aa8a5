"""Serving launcher: batched prefill + decode with continuous batching.

Usage:
  python -m repro.launch.serve --arch qwen2-1.5b --smoke \
      --n-requests 8 --prompt-len 16 --gen-len 24
"""
from __future__ import annotations

import argparse
import asyncio
import json

import jax
import numpy as np

from repro.configs.registry import get_config, get_smoke_config
from repro.core.plan import AttentionPolicy, GemmPolicy
from repro.distributed import tp as TP
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as T
from repro.obs import NULL_OBS, Observability, Timer
from repro.serving.engine import ServeConfig, ServingEngine
from repro.serving.frontend import AsyncServingEngine
from repro.serving.scheduler import Scheduler


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch-slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--n-requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--gemm-backend", default="auto",
                    help="GEMM backend (auto|xla|pallas|"
                         "blockflow|<registered>)")
    ap.add_argument("--gemm-mode", default="auto",
                    choices=["auto", "dc", "dm"],
                    help="paper access mode; auto = per-shape sysmodel pick")
    ap.add_argument("--pack-weights", action="store_true",
                    help="lay weights out block-major once (resident)")
    ap.add_argument("--weight-dtype", default=None, choices=["int8"],
                    help="int8 → quantized W8A8 GEMM route (docs/quant.md); "
                         "with --pack-weights the int8 blocks stay resident")
    ap.add_argument("--attn-backend", default="auto",
                    help="attention backend (auto|fused|unfused|paged|"
                         "<registered>); "
                         "fused = the offset-aware flash kernel for prefill "
                         "AND decode (docs/attention.md); paged = the "
                         "block-table paged KV cache with page-bound "
                         "admission and preemption (docs/serving.md)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="paged backends: tokens per KV page (the paged "
                         "kernel's key-block size)")
    ap.add_argument("--kv-dtype", default=None, choices=["int8"],
                    help="paged backends: int8 → quantized KV pages with "
                         "per-page-per-head scales, dequantized inside the "
                         "paged kernel — ~2x resident requests per pool "
                         "byte (docs/quant.md#kv-pages)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree: builds a (data, model) "
                         "host mesh with a model axis of this size and "
                         "runs prefill/decode sharded over it — "
                         "column/row-parallel GEMMs, head-sharded "
                         "attention, per-shard paged KV pools "
                         "(docs/serving.md). Needs len(jax.devices()) "
                         "divisible by --tp")
    ap.add_argument("--cache-pages", type=int, default=None,
                    help="paged backends: total pages in the KV pool; "
                         "default = the contiguous-equivalent "
                         "batch_slots * ceil(max_len / page_size). Smaller "
                         "values oversubscribe memory (page-bound "
                         "admission + preemption)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="paged backends: share full prompt-prefix KV "
                         "pages across requests (copy-on-write radix "
                         "cache — docs/serving.md#prefix-cache)")
    ap.add_argument("--prefix-watermark", type=int, default=0,
                    help="with --prefix-cache: evict cold cached entries "
                         "each step until this many pool pages are free "
                         "(0 = evict only on demand)")
    ap.add_argument("--spec", default=None, metavar="DRAFTER",
                    help="speculative decoding on the continuous-batching "
                         "path: 'ngram' (prompt-lookup self-speculation) or "
                         "'draft:<arch>' (a registry draft model, e.g. "
                         "draft:smollm-135m) — greedy only; step() then "
                         "emits bursts of verified tokens "
                         "(docs/serving.md#speculative-decoding)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="with --spec: draft budget per request per step")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="tokens of prefill per engine step (chunked "
                         "prefill, interleaved with decode to bound decode "
                         "latency jitter); default: whole prompt at submit")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="continuous-batching workload: prepend this many "
                         "shared tokens to every prompt (the system-prompt "
                         "traffic shape the prefix cache serves)")
    ap.add_argument("--async-demo", type=int, default=0, metavar="N",
                    help="also run N concurrent requests through the "
                         "AsyncServingEngine streaming frontend "
                         "(serving/frontend.py)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="enable observability (repro/obs) and write a "
                         "Perfetto/Chrome trace of the serving engines to "
                         "PATH — one track per engine phase plus one async "
                         "track per request id; open at ui.perfetto.dev "
                         "(docs/observability.md)")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="enable observability and write the metrics "
                         "registry snapshot (counters/gauges/histograms) "
                         "to PATH as JSON")
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    policy = GemmPolicy(backend=args.gemm_backend, mode=args.gemm_mode)
    attn = AttentionPolicy(backend=args.attn_backend,
                           page_size=args.page_size)
    if args.kv_dtype and not args.attn_backend.startswith("paged"):
        ap.error("--kv-dtype requires a paged attention backend "
                 "(--attn-backend paged)")
    mesh = make_host_mesh(model=args.tp) if args.tp > 1 else None
    scheduler = (Scheduler(prefill_chunk=args.prefill_chunk)
                 if args.prefill_chunk else None)
    # one recorder across the continuous-batching and async engines: their
    # phase spans land on shared tracks, request ids on async tracks
    obs = (Observability() if (args.trace_out or args.metrics_json)
           else NULL_OBS)
    print(f"[serve] arch={cfg.name} slots={args.batch_slots} "
          f"max_len={args.max_len} gemm={policy.resolved_backend()}/"
          f"{policy.mode} attn={attn.resolved_backend()} "
          f"packed={args.pack_weights} "
          f"weight_dtype={args.weight_dtype or 'native'}")
    if mesh is not None:
        print(f"[serve] TP: mesh={dict(mesh.shape)} "
              f"(model axis = {args.tp}-way tensor parallel)")
    sc = ServeConfig(
        batch_slots=args.batch_slots, max_len=args.max_len,
        temperature=args.temperature, gemm=policy, attention=attn,
        pack_weights=args.pack_weights, weight_dtype=args.weight_dtype,
        kv_dtype=args.kv_dtype, cache_pages=args.cache_pages, mesh=mesh)
    if sc.paged():
        print(f"[serve] paged KV: page_size={args.page_size} pages="
              f"{args.cache_pages or 'contiguous-equivalent'} "
              f"kv_dtype={args.kv_dtype or 'cache-dtype'} "
              f"prefix_cache={args.prefix_cache} "
              f"prefill_chunk={args.prefill_chunk or 'whole-prompt'}")
    elif args.prefix_cache:
        ap.error("--prefix-cache requires a paged attention backend "
                 "(--attn-backend paged)")
    key = jax.random.PRNGKey(args.seed)
    if mesh is None:
        params, axes = T.init_model(key, cfg)
    else:   # created in place, shard by shard: a TP model may fit no chip
        params, axes = T.init_sharded_model(
            key, cfg, TP.make_context(mesh, None, cfg))
    engine = ServingEngine(cfg, params, sc, axes=axes)

    rng = np.random.default_rng(args.seed)
    # batched generate path (one full batch)
    prompts = rng.integers(0, cfg.vocab,
                           (args.batch_slots, args.prompt_len)).astype(np.int32)
    with Timer() as tm:
        out = engine.generate(prompts, args.gen_len)
    dt = tm.dt
    tput = args.batch_slots * args.gen_len / dt
    print(f"[serve] batched generate: {out.shape} in {dt:.2f}s "
          f"({tput:.1f} tok/s)")

    # continuous-batching path (slot admission needs position-masked cache
    # updates; SSM/hybrid recurrent state has none, so multi-slot submit is
    # refused — see ServingEngine.submit)
    if cfg.family in ("ssm", "hybrid") and args.batch_slots > 1:
        print("[serve] continuous batching skipped: ssm/hybrid families "
              "support slot admission only with --batch-slots 1")
        _write_obs(args, obs)
        return 0
    spec = None
    if args.spec:
        if args.temperature > 0:
            ap.error("--spec requires greedy sampling (--temperature 0)")
        from repro.serving.spec_decode import make_drafter
        spec = make_drafter(args.spec, k=args.spec_k,
                            max_len=args.max_len, smoke=args.smoke,
                            seed=args.seed + 1)
        print(f"[serve] speculative decoding: {args.spec} k={args.spec_k}")
    sc2 = ServeConfig(
        batch_slots=args.batch_slots, max_len=args.max_len, gemm=policy,
        attention=attn, pack_weights=args.pack_weights,
        weight_dtype=args.weight_dtype, kv_dtype=args.kv_dtype,
        cache_pages=args.cache_pages,
        mesh=mesh, prefix_cache=args.prefix_cache and sc.paged(),
        prefix_watermark=args.prefix_watermark, scheduler=scheduler,
        spec=spec, obs=obs)
    engine2 = ServingEngine(cfg, params, sc2, axes=axes)
    lo = max(1, min(4, args.prompt_len))
    shared = rng.integers(0, cfg.vocab, args.shared_prefix_len).tolist()
    pending = [shared + rng.integers(0, cfg.vocab,
                                     rng.integers(lo, args.prompt_len + 1))
               .tolist() for _ in range(args.n_requests)]
    done_tokens = 0
    live = 0
    with Timer() as tm:
        while pending or live:
            while pending:
                slot = engine2.submit(pending[0])
                if slot is None:
                    break
                pending.pop(0)
                live += 1
            stepped = engine2.step()
            # spec engines emit {handle: [tokens]} bursts, plain ones
            # {handle: token}
            done_tokens += sum(
                len(t) if isinstance(t, list) else 1
                for t in stepped.values())
            # retire a random live request occasionally to exercise
            # recycling (cancel frees the slot — and, when paged, its
            # pool pages)
            if live and done_tokens % 29 == 0 and stepped:
                engine2.cancel(next(iter(stepped)))
                live -= 1
            if done_tokens > args.n_requests * args.gen_len:
                break
            live = int(engine2.slot_live.sum())
    dt = tm.dt
    print(f"[serve] continuous batching: {done_tokens} tokens in {dt:.2f}s "
          f"({done_tokens / max(dt, 1e-9):.1f} tok/s)")
    print(f"[serve] stats: {engine2.stats()}")

    if args.async_demo > 0:
        engine3 = ServingEngine(cfg, params, sc2, axes=axes)
        aeng = AsyncServingEngine(engine3)

        async def one(i: int) -> int:
            prompt = (shared + rng.integers(
                0, cfg.vocab, max(lo, args.prompt_len // 2)).tolist())
            n = 0
            async for _tok in aeng.stream(prompt, args.gen_len,
                                          priority=i % 2):
                n += 1
            return n

        async def demo():
            return await asyncio.gather(
                *(one(i) for i in range(args.async_demo)))

        with Timer() as tm:
            counts = asyncio.run(demo())
        dt = tm.dt
        print(f"[serve] async streaming: {args.async_demo} concurrent "
              f"requests, {sum(counts)} tokens in {dt:.2f}s "
              f"({sum(counts) / max(dt, 1e-9):.1f} tok/s)")
        print(f"[serve] async stats: {engine3.stats()}")
        print(f"[serve] async slo: {json.dumps(aeng.slo_report())}")
    _write_obs(args, obs)
    return 0


def _write_obs(args, obs) -> None:
    """Write the requested observability artifacts (no-op when neither
    --trace-out nor --metrics-json was given)."""
    if args.trace_out:
        n = obs.trace.write(args.trace_out)
        print(f"[serve] trace: {n} events -> {args.trace_out} "
              f"(open at https://ui.perfetto.dev)")
    if args.metrics_json:
        with open(args.metrics_json, "w") as f:
            json.dump(obs.metrics.snapshot(), f, indent=2, sort_keys=True)
        print(f"[serve] metrics: snapshot -> {args.metrics_json}")


if __name__ == "__main__":
    raise SystemExit(main())
