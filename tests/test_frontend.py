"""AsyncServingEngine: per-request streams over the batched engine.

Plain asyncio.run() inside sync tests (no pytest-asyncio dependency —
the [test] extra stays jax+pytest+hypothesis). The golden property: the
streamed tokens are exactly the engine's submit()/step() streams, under
any number of concurrent consumers.
"""
import asyncio

import jax
import pytest

from repro.configs.registry import get_smoke_config
from repro.core.plan import AttentionPolicy
from repro.models import transformer as T
from repro.serving.engine import ServeConfig, ServingEngine
from repro.serving.frontend import AsyncServingEngine

PAGED8 = AttentionPolicy(backend="paged", page_size=8, block_q=8)


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("smollm-135m", n_layers=2, vocab=64)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def sync_stream(cfg, params, sc, prompt, n):
    eng = ServingEngine(cfg, params, sc)
    h = eng.submit(prompt)
    out = []
    while len(out) < n and (eng.slot_live.any() or eng.wait):
        for hh, t in eng.step().items():
            if hh == h:
                out.append(t)
    return out[:n]


def test_stream_matches_engine(setup):
    cfg, params = setup
    sc = ServeConfig(batch_slots=2, max_len=32)
    want = sync_stream(cfg, params, sc, [3, 1, 4, 1, 5], 6)
    aeng = AsyncServingEngine(ServingEngine(cfg, params, sc))
    got = asyncio.run(aeng.complete([3, 1, 4, 1, 5], 6))
    assert got == want
    assert aeng.in_flight == 0


def test_concurrent_streams_match_solo_runs(setup):
    """N concurrent consumers through one pump: every stream equals its
    solo engine run — batching is invisible to each consumer."""
    cfg, params = setup
    sc = ServeConfig(batch_slots=2, max_len=32, attention=PAGED8,
                     cache_pages=8)
    prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [2, 4]]

    async def run_all(aeng):
        return await asyncio.gather(
            *(aeng.complete(p, 5, priority=i % 2)
              for i, p in enumerate(prompts)))

    aeng = AsyncServingEngine(ServingEngine(cfg, params, sc))
    got = asyncio.run(run_all(aeng))
    for p, stream in zip(prompts, got):
        assert stream == sync_stream(cfg, params, ServeConfig(
            batch_slots=2, max_len=32, attention=PAGED8, cache_pages=8),
            p, 5), p
    assert aeng.engine.pool.free_pages == aeng.engine.pool.n_pages


def test_breaking_out_cancels_request(setup):
    cfg, params = setup
    sc = ServeConfig(batch_slots=1, max_len=32, attention=PAGED8)
    eng = ServingEngine(cfg, params, sc)
    aeng = AsyncServingEngine(eng)

    async def take_two():
        got = []
        async for tok in aeng.stream([1, 2, 3], 10):
            got.append(tok)
            if len(got) == 2:
                break                    # consumer walks away
        return got

    got = asyncio.run(take_two())
    assert len(got) == 2
    assert aeng.in_flight == 0
    assert eng.pool.free_pages == eng.pool.n_pages   # pages released


def test_stream_closes_at_engine_horizon(setup):
    """A request retiring at max_len stops producing; its stream must end
    rather than hang, even while other requests keep running."""
    cfg, params = setup
    sc = ServeConfig(batch_slots=2, max_len=8)
    aeng = AsyncServingEngine(ServingEngine(cfg, params, sc))

    async def run():
        return await asyncio.gather(aeng.complete([1, 2, 3], 50),
                                    aeng.complete([4, 5, 6], 4))

    long, short = asyncio.run(run())
    assert len(short) == 4
    assert 0 < len(long) < 50            # horizon-bounded, not hung
    assert aeng.in_flight == 0


def test_queued_overflow_is_served_after_capacity_frees(setup):
    """More concurrent streams than slots: the surplus queues in the
    frontend and is admitted as capacity frees — every stream completes."""
    cfg, params = setup
    sc = ServeConfig(batch_slots=2, max_len=32, attention=PAGED8,
                     cache_pages=8)
    aeng = AsyncServingEngine(ServingEngine(cfg, params, sc))

    async def run():
        return await asyncio.gather(
            *(aeng.complete([10 + i, 20 + i], 3) for i in range(5)))

    streams = asyncio.run(run())
    assert all(len(s) == 3 for s in streams)
    assert aeng.in_flight == 0


def test_stream_rejects_nonpositive_budget(setup):
    cfg, params = setup
    aeng = AsyncServingEngine(ServingEngine(
        cfg, params, ServeConfig(batch_slots=1, max_len=16)))
    with pytest.raises(ValueError, match="n_tokens"):
        asyncio.run(aeng.complete([1, 2], 0))
