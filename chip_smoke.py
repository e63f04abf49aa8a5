#!/usr/bin/env python3
"""Smoke run of the serving path on a TPU: build, serve, check, exit.

    python3 chip_smoke.py [--seed N]     # one chip
    python3 chip_smoke.py --tp 4         # four chips, tensor-parallel only

One chip: qwen2-1.5b at full width and depth with random bf16 weights from
``--seed``, served by ``ServingEngine`` through ``submit``/``step`` with the
paged KV pool and the prefix cache, once on bf16 pages and once on int8
pages. GEMMs take ``GemmPolicy()`` (``auto``: the Pallas kernel on a TPU),
attention the paged kernel. Each phase checks that every request got its
full token count, that the prefix cache hit, that the compiled prefill and
decode programs call the GEMM and paged attention kernels as compiled TPU
code (``tpu_custom_call``), and that the engine's logits agree with the same
params run through XLA GEMMs and unfused attention on the same chip: at the
last position of a prefill, and for one decoded token after it.

``--tp 4``: qwen3-8b at full width and depth (16.4 GB of bf16 weights, more
than one chip holds), created already sharded over a (data=1, model=4) mesh
under the config's own sharding rules and served on paged bf16 pages; then
a 2-layer cut of it at full width is prefilled and decodes one token on the
mesh and on one device, and the logits must agree.

Throughput, compile seconds and peak device memory are printed for
information; they are not benchmark results. Every check raises on failure,
so the process exits non-zero; on success the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU it exits non-zero before any phase. Everything runs in this
one process: a child process could not take the chip from it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional, Tuple

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.registry import get_config  # noqa: E402
from repro.core import api  # noqa: E402
from repro.core.plan import AttentionPolicy, GemmPolicy, on_tpu  # noqa: E402
from repro.distributed import tp as TP  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.serving.engine import ServeConfig, ServingEngine  # noqa: E402

# Relative L2 error of last-position logits (over the vocabulary, all rows)
# between the kernel path and its reference. bf16 weights and activations:
# this is the bf16 tolerance of the parity harness (tests/parity.py TOLS),
# applied to the whole logit vector instead of per element. int8 pages add
# up to half a scale step (scale = 2 x the page's first-row amax / 127,
# docs/quant.md) to every cached K/V element, so they get twice the budget.
LOGIT_RTOL = {None: 5e-2, "int8": 1e-1}


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


@dataclasses.dataclass(frozen=True)
class Traffic:
    """One phase's request mix and pool. Prompts are a shared prefix plus a
    unique suffix; suffix lengths are spread evenly over ``suffix`` so the
    power-of-two prefill buckets are the same in the warm-up and the
    measured pass (no compile inside the measured pass)."""

    n_requests: int = 16
    shared_prefix: int = 256
    suffix: tuple = (129, 768)        # prompt lengths 385..1024
    gen: int = 32
    slots: int = 8
    pages: int = 2048                 # x 16 tokens ~ 0.9 GB of qwen2 KV
    page_size: int = 16
    max_len: int = 1280
    ref_len: int = 256                # reference prompt length (a bucket)


ONE_CHIP = Traffic()
TP4 = Traffic(n_requests=8, shared_prefix=128, suffix=(129, 256), gen=16,
              slots=4, pages=512, max_len=512, ref_len=256)


def make_prompts(vocab: int, traffic: Traffic, seed: int) -> List[List[int]]:
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, vocab, traffic.shared_prefix).tolist()
    lengths = np.linspace(*traffic.suffix, traffic.n_requests).astype(int)
    return [shared + rng.integers(0, vocab, int(n)).tolist()
            for n in lengths]


def make_engine(cfg: ModelConfig, params, traffic: Traffic, *,
                kv_dtype: Optional[str] = None, mesh=None,
                axes=None) -> ServingEngine:
    sc = ServeConfig(
        batch_slots=traffic.slots, max_len=traffic.max_len,
        gemm=GemmPolicy(),
        attention=AttentionPolicy(backend="paged",
                                  page_size=traffic.page_size),
        kv_dtype=kv_dtype, cache_pages=traffic.pages, prefix_cache=True,
        mesh=mesh)
    return ServingEngine(cfg, params, sc, axes=axes)


def serve(eng: ServingEngine, prompts: List[List[int]], gen: int) -> Dict:
    """Drive ``prompts`` through submit/step until each has ``gen`` tokens
    (a request is finished by cancelling it, as the streaming frontend
    does). Returns the streams and the wall time, ended by the host sync
    of the last sampled token."""
    pending = list(prompts)
    streams: Dict[int, List[int]] = {}
    live = set()
    t0 = time.perf_counter()
    while pending or live:
        while pending:
            rid = eng.submit(pending[0])
            if rid is None:
                break
            pending.pop(0)
            streams[rid] = []
            live.add(rid)
        check(bool(live), "no request could be admitted")
        stepped = eng.step()
        check(bool(stepped), "step() made no progress")
        for rid, tok in stepped.items():
            streams[rid].append(int(tok))
            if len(streams[rid]) == gen:
                eng.cancel(rid)
                live.discard(rid)
    return {"streams": streams, "seconds": time.perf_counter() - t0}


def serve_phase(cfg: ModelConfig, eng: ServingEngine, traffic: Traffic,
                seed: int) -> Dict:
    """A warm-up pass that compiles every program the traffic needs, then
    the measured pass on fresh prompts of the same lengths."""
    warm = serve(eng, make_prompts(cfg.vocab, traffic, seed), traffic.gen)
    run = serve(eng, make_prompts(cfg.vocab, traffic, seed + 1), traffic.gen)
    for r in (warm, run):
        check(len(r["streams"]) == traffic.n_requests,
              f"{len(r['streams'])} of {traffic.n_requests} requests served")
        short = {rid: len(s) for rid, s in r["streams"].items()
                 if len(s) != traffic.gen}
        check(not short, f"requests without {traffic.gen} tokens: {short}")
    stats = eng.stats()
    check(stats.get("prefix_hits", 0) > 0, f"no prefix hits: {stats}")
    n_tok = traffic.n_requests * traffic.gen
    return {"warm_s": warm["seconds"], "run_s": run["seconds"],
            "tokens_per_s": n_tok / run["seconds"], "stats": stats}


def compiled_text(jitted, *args) -> str:
    return jitted.lower(*args).compile().as_text()


# the Pallas kernels each compiled step must call, by the name of the jitted
# kernel entry point that the custom call's op_name carries
STEP_KERNELS = ("matrixflow_gemm", "paged_attention")


def kernels_compiled(eng: ServingEngine, traffic: Traffic) -> None:
    """The engine's compiled decode and prefill programs must call the
    GEMM and paged attention kernels as compiled TPU code
    (``tpu_custom_call``); interpret mode would leave none."""
    B, L = traffic.slots, traffic.ref_len
    bt = jnp.asarray(eng.block_tables)
    zeros = jnp.zeros((B, 1), jnp.int32)
    decode = compiled_text(eng.decode, eng.params, zeros, zeros, eng.caches,
                           bt)
    batch = {"tokens": jnp.zeros((B, L), jnp.int32),
             "positions": jnp.zeros((B, L), jnp.int32),
             "last_cols": jnp.zeros((B,), jnp.int32), "block_tables": bt}
    prefill = compiled_text(eng.prefill, eng.params, batch, eng.caches)
    for name, text in (("decode", decode), ("prefill", prefill)):
        calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
        for kernel in STEP_KERNELS:
            check(any(kernel in ln for ln in calls),
                  f"compiled {name} step calls no compiled {kernel} kernel")


def reference_batch(eng: ServingEngine, vocab: int, L: int, seed: int):
    """``B`` prompts of ``L + 1`` tokens, each row on its own pages: the
    prefill batch of their first ``L`` tokens, and the token each row
    decodes next."""
    B, nb = eng.sc.batch_slots, eng.n_blocks
    per_row = -(-(L + 1) // eng.pool.page_size)
    check(B * per_row <= eng.pool.n_pages, "pool too small for the batch")
    tokens = np.random.default_rng(seed).integers(0, vocab, (B, L + 1))
    bt = np.zeros((B, nb), np.int32)
    bt[:, :per_row] = np.arange(B * per_row).reshape(B, per_row)
    return tokens.astype(np.int32), {
        "tokens": jnp.asarray(tokens[:, :L], jnp.int32),
        "positions": jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L)),
        "last_cols": jnp.full((B,), L - 1, jnp.int32),
        "block_tables": jnp.asarray(bt)}


def engine_logits(eng: ServingEngine, tokens: np.ndarray, batch
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Last-position logits of the engine's own prefill program over
    ``batch``, then of its decode program for each row's next token
    against the caches that prefill wrote. Every slot starts empty; the
    serving state is spent afterwards."""
    B, L = batch["tokens"].shape
    eng._set_slot_lens({b: 0 for b in range(B)})
    step = {"tokens": jnp.asarray(tokens[:, L:]),
            "positions": jnp.full((B, 1), L, jnp.int32)}
    if eng.tp is not None:
        batch = {k: TP.replicate(v, eng.tp) for k, v in batch.items()}
        step = {k: TP.replicate(v, eng.tp) for k, v in step.items()}
    prefill, caches = eng.prefill(eng.params, batch, eng.caches)
    decode, _ = eng.decode(eng.params, step["tokens"], step["positions"],
                           caches, batch["block_tables"])
    return np.asarray(prefill, np.float32), np.asarray(decode, np.float32)


def reference_logits(cfg: ModelConfig, params, tokens: np.ndarray
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Logits at the last two positions (the prefill's last token and the
    decoded one) through XLA GEMMs and unfused attention."""
    def fwd(p, t):
        with api.use_policy(GemmPolicy(backend="xla")), \
                api.use_attention_policy(AttentionPolicy(backend="unfused")):
            logits, _, _ = T.forward(p, cfg, {"tokens": t}, remat=False)
        return logits[:, -2], logits[:, -1]
    out = jax.jit(fwd)(params, jnp.asarray(tokens))
    return tuple(np.asarray(x, np.float32) for x in out)


def compare(got: np.ndarray, ref: np.ndarray) -> Dict:
    check(bool(np.isfinite(got).all()), "non-finite logits")
    rel = float(np.linalg.norm(got - ref) / np.linalg.norm(ref))
    top1 = float((got.argmax(-1) == ref.argmax(-1)).mean())
    return {"rel_l2": rel, "top1_agreement": top1}


def check_logits(out: Dict, got, ref, rtol: float, what: str) -> None:
    """Compare the prefill and decode logits pairwise; ``out`` gets one
    entry per step."""
    for step, g, r in zip(("prefill", "decode"), got, ref):
        out[step] = compare(g, r)
        check(out[step]["rel_l2"] <= rtol,
              f"{step} logits vs {what}: {out[step]}")


def one_chip_phase(cfg: ModelConfig, params, traffic: Traffic, seed: int, *,
                   kv_dtype: Optional[str]) -> Dict:
    """Serve on one device, then check kernels (on a TPU: interpreted
    kernels leave nothing to find) and logits."""
    eng = make_engine(cfg, params, traffic, kv_dtype=kv_dtype)
    out = serve_phase(cfg, eng, traffic, seed)
    if on_tpu():
        kernels_compiled(eng, traffic)
    tokens, batch = reference_batch(eng, cfg.vocab, traffic.ref_len, seed)
    out["logits"] = {}
    check_logits(out["logits"], engine_logits(eng, tokens, batch),
                 reference_logits(cfg, params, tokens), LOGIT_RTOL[kv_dtype],
                 "XLA/unfused reference")
    return out


def tp_phase(cfg: ModelConfig, tp: int, traffic: Traffic, seed: int) -> Dict:
    """Serve ``cfg`` tensor-parallel over ``tp`` devices with its params
    created in place, then compare a 2-layer cut on the mesh with one
    device."""
    mesh = make_host_mesh(model=tp)
    ctx = TP.make_context(mesh, None, cfg)
    key = jax.random.PRNGKey(seed)
    params, axes = T.init_sharded_model(key, cfg, ctx)
    eng = make_engine(cfg, params, traffic, mesh=mesh, axes=axes)
    check(eng.kv_shards() == tp, f"KV pool split {eng.kv_shards()} ways")
    out = serve_phase(cfg, eng, traffic, seed)
    del eng, params

    cut = dataclasses.replace(cfg, n_layers=2)
    one, _ = T.init_model(key, cut)
    sharded, cut_axes = T.init_sharded_model(key, cut, ctx)
    e_one = make_engine(cut, one, traffic)
    e_tp = make_engine(cut, sharded, traffic, mesh=mesh, axes=cut_axes)
    tokens, batch = reference_batch(e_one, cut.vocab, traffic.ref_len, seed)
    out["logits"] = {}
    check_logits(out["logits"], engine_logits(e_tp, tokens, batch),
                 engine_logits(e_one, tokens, batch), LOGIT_RTOL[None],
                 f"one device (TP={tp})")
    return out


def peak_bytes() -> Optional[int]:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def report(name: str, out: Dict, compile_s: float) -> None:
    s = out["stats"]
    print(f"[smoke] {name}: served {s['decode_tokens']} decode tokens; "
          f"prefix hits {s['prefix_hits']} (hit rate "
          f"{s['prefix_hit_rate']}); logits vs reference: " + "; ".join(
              f"{step} rel_l2={c['rel_l2']!r} greedy agreement="
              f"{c['top1_agreement']!r}"
              for step, c in out["logits"].items()))
    print(f"[smoke] {name} (information only): {out['tokens_per_s']!r} "
          f"tokens/s in the measured pass ({out['run_s']!r} s); warm-up pass "
          f"{out['warm_s']!r} s; backend compile so far {compile_s!r} s; "
          f"peak device bytes {peak_bytes()!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tp", type=int, default=1,
                    help="run only the tensor-parallel qwen3-8b phase over "
                         "this many chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"[smoke] device: {device['kind']} x{device['count']} "
          f"({device['platform']})", flush=True)
    if device["platform"] != "tpu":
        print(f"[smoke] FAIL: needs a TPU, JAX found platform "
              f"{device['platform']!r}", file=sys.stderr)
        return 1
    print(f"[smoke] compile cache: {use_compile_cache()}")
    compile_s = [0.0]

    def on_duration(event: str, seconds: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += seconds

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    if args.tp > 1:
        cfg = get_config("qwen3-8b")
        out = tp_phase(cfg, args.tp, TP4, args.seed)
        report(f"{cfg.name} TP={args.tp} paged bf16", out, compile_s[0])
    else:
        cfg = get_config("qwen2-1.5b")
        params, _ = T.init_model(jax.random.PRNGKey(args.seed), cfg)
        for kv_dtype in (None, "int8"):
            out = one_chip_phase(cfg, params, ONE_CHIP, args.seed,
                                 kv_dtype=kv_dtype)
            report(f"{cfg.name} paged {kv_dtype or 'bf16'}", out,
                   compile_s[0])
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
