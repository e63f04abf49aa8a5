"""Batched serving example: prefill + decode with a KV cache and
continuous batching over slots (the decode_* shape cells' code path).

Run:  PYTHONPATH=src python examples/serve_lm.py
"""
import time

import jax
import numpy as np

from repro.configs.registry import get_smoke_config
from repro.models import transformer as T
from repro.serving.engine import ServeConfig, ServingEngine


def main():
    cfg = get_smoke_config("qwen2-1.5b", n_layers=2, vocab=512)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    engine = ServingEngine(cfg, params,
                           ServeConfig(batch_slots=4, max_len=64))
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab, (4, 12)).astype(np.int32)

    t0 = time.perf_counter()
    out = engine.generate(prompts, n_tokens=16)
    dt = time.perf_counter() - t0
    print(f"[serve] generated {out.shape[0]}×{out.shape[1]} tokens "
          f"in {dt:.2f}s ({out.size / dt:.1f} tok/s)")
    print("[serve] first sequence:", out[0].tolist())

    # continuous batching: requests trickle in, slots recycle
    engine2 = ServingEngine(cfg, params,
                            ServeConfig(batch_slots=2, max_len=32))
    reqs = [rng.integers(0, cfg.vocab, n).tolist() for n in (5, 7, 4)]
    s0 = engine2.submit(reqs[0])
    engine2.submit(reqs[1])
    assert engine2.submit(reqs[2]) is None      # full → queued by caller
    for _ in range(6):
        engine2.step()
    engine2.cancel(s0)                          # request 0 finishes
    s2 = engine2.submit(reqs[2])                # slot recycled
    assert s2 == s0
    for _ in range(4):
        engine2.step()
    print("[serve] continuous batching OK — slot", s0, "recycled for req 2")

    # paged KV cache: page-bound admission (docs/serving.md) — a pool half
    # the contiguous budget still serves all 4 requests concurrently
    from repro.core.plan import AttentionPolicy
    engine3 = ServingEngine(cfg, params, ServeConfig(
        batch_slots=4, max_len=32,
        attention=AttentionPolicy(backend="paged", page_size=8,
                                  block_q=8),
        cache_pages=8))
    rids = [engine3.submit(rng.integers(0, cfg.vocab, 3).tolist())
            for _ in range(4)]
    assert all(r is not None for r in rids)
    for _ in range(8):
        engine3.step()
    print(f"[serve] paged: 4 live requests on a pool of "
          f"{engine3.pool.n_pages} pages "
          f"({engine3.pool.pages_in_use} in use, "
          f"{engine3.n_preemptions} preemptions)")


if __name__ == "__main__":
    main()
