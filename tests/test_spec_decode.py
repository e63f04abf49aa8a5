"""Speculative decoding: drafter units, token-identity gates (paged,
fused, int8 KV, prefix-COW, forced preempt/resume), rollback accounting,
and the draft-model path's full-acceptance sanity check."""
import asyncio

import jax
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.core.plan import AttentionPolicy
from repro.models import transformer as T
from repro.serving.engine import ServeConfig, ServingEngine
from repro.serving.spec_decode import (DraftModelDrafter, NGramDrafter,
                                       make_drafter)

PAGED8 = AttentionPolicy(backend="paged", page_size=8, block_q=8)
FUSED8 = AttentionPolicy(backend="fused", block_q=8, block_k=8)


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("smollm-135m", n_layers=2, vocab=64)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


# -- NGramDrafter units ------------------------------------------------------
def test_ngram_proposes_most_recent_continuation():
    d = NGramDrafter(k=3, ngram=2)
    # suffix (1, 2) occurred at index 0 (→ 9, 9, 9) and index 5 (→ 7, 8);
    # the most recent match wins
    ctx = [1, 2, 9, 9, 9, 1, 2, 7, 8, 1, 2]
    assert d.draft(ctx, 3) == [7, 8, 1]


def test_ngram_falls_back_to_shorter_ngram():
    d = NGramDrafter(k=2, ngram=3, min_ngram=1)
    # the trailing 3-gram and 2-gram only occur flush against the suffix;
    # the 1-gram [2] occurred earlier with a continuation
    assert d.draft([2, 5, 1, 3, 2], 2) == [5, 1]


def test_ngram_no_match_proposes_nothing():
    d = NGramDrafter(k=4)
    assert d.draft([1, 2, 3, 4, 5], 4) == []
    assert d.draft([7], 4) == []              # too short to self-match
    assert d.draft([3, 3, 3], 0) == []        # engine trimmed budget to 0


def test_ngram_respects_draft_budget():
    d = NGramDrafter(k=8, ngram=1)
    ctx = [5, 1, 2, 3, 4, 5]
    assert d.draft(ctx, 2) == [1, 2]          # per-call cap below k
    assert NGramDrafter(k=2, ngram=1).draft(ctx, 8) == [1, 2]  # instance cap


def test_ngram_validates_arguments():
    with pytest.raises(ValueError, match="ngram"):
        NGramDrafter(k=0)
    with pytest.raises(ValueError, match="ngram"):
        NGramDrafter(ngram=1, min_ngram=2)


def test_make_drafter_rejects_unknown_spec():
    with pytest.raises(ValueError, match="unknown drafter"):
        make_drafter("nope")


# -- engine validation -------------------------------------------------------
def test_spec_requires_greedy(setup):
    cfg, params = setup
    with pytest.raises(ValueError, match="greedy"):
        ServingEngine(cfg, params, ServeConfig(
            batch_slots=2, max_len=32, temperature=0.7,
            spec=NGramDrafter()))


def test_spec_rejects_bad_k(setup):
    cfg, params = setup

    class BadDrafter:
        k = 0

    with pytest.raises(ValueError, match="k >= 1"):
        ServingEngine(cfg, params, ServeConfig(
            batch_slots=2, max_len=32, spec=BadDrafter()))


# -- token-identity gates ----------------------------------------------------
def _run_to_retirement(cfg, params, sc, prompts):
    """Serve ``prompts`` to natural retirement (max_len drain); returns
    {i: full stream} keyed by prompt index, plus the engine."""
    eng = ServingEngine(cfg, params, sc)
    outs = {i: [] for i in range(len(prompts))}
    hmap = {}
    pending = list(enumerate(prompts))
    for _ in range(600):
        while pending:
            i, p = pending[0]
            h = eng.submit(list(p))
            if h is None:
                break
            hmap[h] = i
            pending.pop(0)
        stepped = eng.step()
        for h, t in stepped.items():
            outs[hmap[h]].extend(t if isinstance(t, list) else [t])
        if not pending and not eng.slot_live.any() \
                and not (eng.paged and eng.wait):
            break
    assert not pending and not eng.slot_live.any()
    return outs, eng


def _prompts(n, seed=0, lo=3, hi=14):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 64, rng.integers(lo, hi)).tolist()
            for _ in range(n)]


@pytest.mark.parametrize("attn", [PAGED8, FUSED8],
                         ids=["paged", "fused"])
def test_spec_streams_token_identical(setup, attn):
    """The tentpole gate: speculative greedy streams — run all the way
    through the max_len drain — equal non-speculative streams exactly,
    on the paged AND fused (contiguous rollback) backends."""
    cfg, params = setup
    prompts = _prompts(3, seed=2)
    base = dict(batch_slots=3, max_len=32, attention=attn)
    want, _ = _run_to_retirement(cfg, params, ServeConfig(**base), prompts)
    got, eng = _run_to_retirement(
        cfg, params, ServeConfig(**base, spec=NGramDrafter(k=4)), prompts)
    assert got == want
    assert eng.spec_accepted > 0          # speculation actually engaged
    if eng.paged:
        eng.pool.check()
        assert eng.pool.free_pages == eng.pool.n_pages


def test_spec_rollback_returns_pages(setup):
    """Rejected drafts must shed their tail pages: the rollback counter
    moves and the pool ends fully reclaimed with invariants intact."""
    cfg, params = setup
    prompts = _prompts(4, seed=3)
    sc = ServeConfig(batch_slots=4, max_len=64, attention=PAGED8,
                     spec=NGramDrafter(k=4))
    _, eng = _run_to_retirement(cfg, params, sc, prompts)
    assert eng.spec_rejected > 0
    assert eng.spec_rollback_pages > 0
    eng.pool.check()
    assert eng.pool.free_pages == eng.pool.n_pages
    st = eng.stats()
    assert st["spec_rollback_pages"] == eng.spec_rollback_pages
    assert 0.0 <= st["spec_acceptance_rate"] <= 1.0


def test_spec_preempt_resume_streams_identical(setup):
    """Speculation under pool pressure: a pool that forces preemption
    mid-stream must still produce non-speculative streams — draft pages
    never preempt anyone (they trim instead), and resume re-prefills
    through the same masked path."""
    cfg, params = setup
    sc = ServeConfig(batch_slots=2, max_len=16, attention=PAGED8,
                     cache_pages=2, spec=NGramDrafter(k=4))
    prompts = [[1, 2, 3], [4, 5, 6]]
    got, eng = _run_to_retirement(cfg, params, sc, prompts)
    assert eng.n_preemptions > 0                   # pressure actually hit
    eng.pool.check()
    assert eng.pool.free_pages == eng.pool.n_pages
    base = ServeConfig(batch_slots=2, max_len=16, attention=PAGED8)
    for i, p in enumerate(prompts):
        want, _ = _run_to_retirement(cfg, params, base, [p])
        assert got[i] == want[0], (i, p)


def test_spec_prefix_cow_streams_identical(setup):
    """Speculation over prefix-cache-shared prompts: verify writes and
    rollback truncates must never touch a shared page — streams equal the
    uncached engine's for every request."""
    cfg, params = setup
    shared = list(range(1, 13))                    # crosses a page boundary
    prompts = [shared + [20 + i] for i in range(3)]
    base = dict(batch_slots=3, max_len=32, attention=PAGED8)
    want, _ = _run_to_retirement(cfg, params, ServeConfig(**base), prompts)
    got, eng = _run_to_retirement(
        cfg, params,
        ServeConfig(**base, prefix_cache=True, spec=NGramDrafter(k=4)),
        prompts)
    assert got == want
    assert eng.prefix.stats()["prefix_hits"] > 0   # sharing actually hit
    eng.prefix.clear()
    eng.pool.check()
    assert eng.pool.free_pages == eng.pool.n_pages


def test_spec_kv_int8_streams_self_consistent(setup):
    """int8 KV pages under speculation: the spec stream must equal the
    non-spec stream at the same kv_dtype — page scales stay a pure
    function of logical content across rollback."""
    cfg, params = setup
    prompts = _prompts(2, seed=4)
    base = dict(batch_slots=2, max_len=32, attention=PAGED8,
                kv_dtype="int8")
    want, _ = _run_to_retirement(cfg, params, ServeConfig(**base), prompts)
    got, eng = _run_to_retirement(
        cfg, params, ServeConfig(**base, spec=NGramDrafter(k=4)), prompts)
    assert got == want
    eng.pool.check()


def test_draft_model_self_draft_accepts_everything(setup):
    """A draft model that IS the target proposes the target's own greedy
    continuation — every draft must be accepted (the acceptance rule is
    exact argmax agreement, not approximation)."""
    cfg, params = setup
    # matching the target's backend keeps near-tied argmaxes in agreement
    drafter = DraftModelDrafter(cfg, params, k=3, max_len=32,
                                attention=PAGED8)
    prompts = _prompts(2, seed=5, lo=3, hi=8)
    base = dict(batch_slots=2, max_len=24, attention=PAGED8)
    want, _ = _run_to_retirement(cfg, params, ServeConfig(**base), prompts)
    got, eng = _run_to_retirement(
        cfg, params, ServeConfig(**base, spec=drafter), prompts)
    assert got == want
    assert eng.spec_rejected == 0
    assert eng.spec_accepted > 0


def test_spec_step_emits_bursts(setup):
    """With spec enabled step() returns {handle: [tokens]} — the repeated
    self-matching prompt makes the n-gram drafter land multi-token
    bursts."""
    cfg, params = setup
    eng = ServingEngine(cfg, params, ServeConfig(
        batch_slots=1, max_len=64, attention=PAGED8,
        spec=NGramDrafter(k=4)))
    h = eng.submit([7, 7, 7, 7, 7, 7])
    total, bursts = 0, []
    for _ in range(30):
        stepped = eng.step()
        if h in stepped:
            assert isinstance(stepped[h], list)
            bursts.append(len(stepped[h]))
            total += len(stepped[h])
        if total >= 10:
            break
    assert total >= 10
    eng.cancel(h)


def test_spec_async_frontend_streams(setup):
    """The streaming frontend must consume spec bursts token-by-token and
    stop at exactly n_tokens."""
    from repro.serving.frontend import AsyncServingEngine
    cfg, params = setup
    eng = ServingEngine(cfg, params, ServeConfig(
        batch_slots=2, max_len=32, attention=PAGED8,
        spec=NGramDrafter(k=4)))
    aeng = AsyncServingEngine(eng)

    solo, _ = _run_to_retirement(
        cfg, params, ServeConfig(batch_slots=2, max_len=32,
                                 attention=PAGED8),
        [[1, 2, 3, 1, 2]])

    async def demo():
        return await asyncio.gather(
            aeng.complete([1, 2, 3, 1, 2], 8),
            aeng.complete([9, 8, 7], 8))

    got = asyncio.run(demo())
    assert [len(g) for g in got] == [8, 8]
    assert got[0] == solo[0][:8]
    eng.pool.check()
    assert eng.pool.free_pages == eng.pool.n_pages
