"""Subprocess body for tests/test_tp_serving.py: TP stream equivalence.

Runs on a *forced* multi-device host (the parent sets
``XLA_FLAGS=--xla_force_host_platform_device_count=4``; conftest.py must
stay 1-device, hence the subprocess isolation) and proves the golden
stream-equivalence gate: a TP=2 tensor-parallel paged serving engine
(ServeConfig.mesh over a (data, model) host mesh — repro/distributed/tp.py)
produces token streams **identical** to the single-device engine on the
same prompts:

  * batched greedy generate();
  * continuous-batching submit()/step() greedy streams;
  * seeded-temperature sampling (same PRNG keys both sides);
  * a forced preempt/resume cycle (a pool too small for both requests —
    the TP engine must preempt, resume, and still match the single-device
    engine, whose host-side scheduling is identical by construction).

The model is an fp32 smoke config with the TP-relevant head shapes
(GQA H=4, Hkv=2 → both shard at TP=2) and the kv_heads override cleared so
the KV pool actually splits. fp32 keeps the only TP-vs-1-device numeric
difference — the row-parallel psum's fp32 summation order — at ~1e-7
relative, far below any argmax/sampling decision boundary.

Exit 0 + "TP-EQUIV PASS <scenario>" markers on success; nonzero with a
traceback on the first divergence.
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir, "src"))

import jax                                                    # noqa: E402
import numpy as np                                            # noqa: E402

from repro.configs.registry import get_smoke_config           # noqa: E402
from repro.core.plan import AttentionPolicy                   # noqa: E402
from repro.launch.mesh import make_host_mesh                  # noqa: E402
from repro.models import transformer as T                     # noqa: E402
from repro.serving.engine import ServeConfig, ServingEngine   # noqa: E402

PAGED = AttentionPolicy(backend="paged", page_size=8, block_q=8)


def build():
    assert len(jax.devices()) >= 2, (
        "runner needs the forced multi-device host; run it via "
        "tests/test_tp_serving.py or set XLA_FLAGS="
        "--xla_force_host_platform_device_count=4")
    # qwen3 smoke = GQA (H=4, Hkv=2) + qk_norm; clear the kv_heads override
    # so TP=2 shards the KV pool (the per-shard paged-cache path), and run
    # fp32 so psum reordering stays under sampling decision noise.
    cfg = get_smoke_config("qwen3-8b", n_layers=2, vocab=64,
                           sharding_overrides=(), dtype="float32")
    params, axes = T.init_model(jax.random.PRNGKey(0), cfg)
    mesh = make_host_mesh(model=2)
    return cfg, params, axes, mesh


def engines(cfg, params, axes, mesh, **sc_kw):
    sc = dict(batch_slots=2, max_len=32, attention=PAGED,
              cache_dtype="float32")
    sc.update(sc_kw)
    base = ServingEngine(cfg, params, ServeConfig(**sc))
    tp = ServingEngine(cfg, params, ServeConfig(**sc, mesh=mesh), axes=axes)
    assert tp.tp is not None and tp.tp.model_size == 2
    assert tp.kv_shards() == 2, "KV pool must actually split at TP=2"
    return base, tp


def scenario_greedy(cfg, params, axes, mesh):
    base, tp = engines(cfg, params, axes, mesh)
    prompts = np.random.default_rng(7).integers(0, 64, (2, 6)).astype(np.int32)
    want = base.generate(prompts, 8)
    got = tp.generate(prompts, 8)
    np.testing.assert_array_equal(got, want)

    base, tp = engines(cfg, params, axes, mesh)
    for eng in (base, tp):
        assert eng.submit([3, 1, 4, 1, 5]) is not None
        assert eng.submit([9, 2, 6]) is not None
    for _ in range(6):
        sb, st = base.step(), tp.step()
        assert sb == st, (sb, st)
    print("TP-EQUIV PASS greedy")


def scenario_temperature(cfg, params, axes, mesh):
    base, tp = engines(cfg, params, axes, mesh, temperature=0.8)
    prompts = np.random.default_rng(11).integers(0, 64, (2, 5)).astype(np.int32)
    want = base.generate(prompts, 8, key=jax.random.PRNGKey(42))
    got = tp.generate(prompts, 8, key=jax.random.PRNGKey(42))
    np.testing.assert_array_equal(got, want)

    base, tp = engines(cfg, params, axes, mesh, temperature=0.8)
    for eng in (base, tp):
        assert eng.submit([5, 4, 3], key=jax.random.PRNGKey(1)) is not None
    for i in range(6):
        k = jax.random.PRNGKey(100 + i)
        sb, st = base.step(key=k), tp.step(key=k)
        assert sb == st, (i, sb, st)
    print("TP-EQUIV PASS temperature")


def scenario_preempt(cfg, params, axes, mesh):
    # 2 pages of 8 tokens = half of 2 slots x max_len 16: decode growth
    # must exhaust the pool and preempt. Both engines share the host-side
    # scheduler, so the preempt/resume choreography — and hence the
    # streams — must match exactly.
    base, tp = engines(cfg, params, axes, mesh, max_len=16, cache_pages=2)
    prompts = [[1, 2, 3], [4, 5, 6]]
    rb = [base.submit(p) for p in prompts]
    rt = [tp.submit(p) for p in prompts]
    assert all(r is not None for r in rb + rt)
    for _ in range(80):
        base.step()
        tp.step()
        if (not base.slot_live.any() and not base.wait
                and not tp.slot_live.any() and not tp.wait):
            break
    assert tp.n_preemptions > 0, "pool pressure never hit — dead scenario"
    assert tp.n_preemptions == base.n_preemptions
    for hb, ht, p in zip(rb, rt, prompts):
        assert base.request_out[hb] == tp.request_out[ht], \
            (p, base.request_out[hb], tp.request_out[ht])
    tp.pool.check()
    assert tp.pool.free_pages == tp.pool.n_pages
    print("TP-EQUIV PASS preempt-resume")


def scenario_prefix(cfg, params, axes, mesh):
    # Prefix cache + COW under TP: one host-side cache drives every
    # shard's identical page slice, so the TP engine must stay in
    # LOCKSTEP with the single-device engine — same streams, same page
    # accounting, same hit/fork counters. The second prompt shares a full
    # page; the third diverges inside it (exercises the sharded-page
    # device copy in _copy_page: page axis is unsharded, head axis is).
    base, tp = engines(cfg, params, axes, mesh, batch_slots=3,
                       prefix_cache=True, cache_pages=12)
    shared = list(range(1, 12))              # 11 tokens: 1 full page + tail
    prompts = [shared + [40, 41], shared + [50, 51],
               shared[:5] + [60, 61, 62, 63]]
    for eng in (base, tp):
        for p in prompts:
            assert eng.submit(p) is not None
    assert tp.prefix.hits == base.prefix.hits > 0
    assert tp.prefix.cow_forks == base.prefix.cow_forks >= 1
    for _ in range(6):
        sb, st = base.step(), tp.step()
        assert sb == st, (sb, st)
        # lockstep page accounting: the TP pool mirrors the base pool
        assert tp.pool.free_pages == base.pool.free_pages
        assert tp.pool.pages_in_use == base.pool.pages_in_use
    assert tp.prefix.stats() == base.prefix.stats()
    tp.pool.check()
    tp.prefix.check()
    print("TP-EQUIV PASS prefix")


def scenario_kv_int8(cfg, params, axes, mesh):
    # Quantized KV pages under TP: the int8 pools shard on Hkv (axis -2)
    # and their (P, Hkv) scale side-tensors on Hkv (axis -1), so each
    # shard quantizes/dequantizes its own heads with its own scales.
    # Streams must match the single-device int8 engine token for token,
    # including across a forced preempt/resume cycle.
    base, tp = engines(cfg, params, axes, mesh, kv_dtype="int8")
    prompts = np.random.default_rng(13).integers(0, 64, (2, 6)).astype(np.int32)
    np.testing.assert_array_equal(tp.generate(prompts, 8),
                                  base.generate(prompts, 8))

    base, tp = engines(cfg, params, axes, mesh, kv_dtype="int8",
                       max_len=16, cache_pages=2)
    for eng in (base, tp):
        assert eng.submit([1, 2, 3]) is not None
        assert eng.submit([4, 5, 6]) is not None
    for _ in range(80):
        base.step()
        tp.step()
        if (not base.slot_live.any() and not base.wait
                and not tp.slot_live.any() and not tp.wait):
            break
    assert tp.n_preemptions > 0, "pool pressure never hit — dead scenario"
    assert tp.n_preemptions == base.n_preemptions
    assert base.request_out == tp.request_out
    tp.pool.check()
    print("TP-EQUIV PASS kv-int8")


SCENARIOS = {"greedy": scenario_greedy, "temperature": scenario_temperature,
             "preempt": scenario_preempt, "prefix": scenario_prefix,
             "kv-int8": scenario_kv_int8}


def main(argv=None):
    argv = argv if argv is not None else sys.argv[1:]
    picks = argv or list(SCENARIOS)
    cfg, params, axes, mesh = build()
    for name in picks:
        SCENARIOS[name](cfg, params, axes, mesh)
    print(f"TP-EQUIV PASS all ({', '.join(picks)})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
