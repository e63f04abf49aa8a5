"""Serving engine: batched generate, continuous batching slots, greedy
determinism, fused-attention parity, retirement/temperature regressions."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.core.plan import AttentionPolicy
from repro.models import transformer as T
from repro.serving.engine import ServeConfig, ServingEngine


@pytest.fixture(scope="module")
def engine():
    cfg = get_smoke_config("qwen2-1.5b", n_layers=2, vocab=64)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    return ServingEngine(cfg, params, ServeConfig(batch_slots=4, max_len=64))


def test_generate_shapes(engine):
    prompts = np.random.default_rng(0).integers(0, 64, (4, 8)).astype(np.int32)
    out = engine.generate(prompts, n_tokens=5)
    assert out.shape == (4, 5)
    assert out.min() >= 0 and out.max() < 64


def test_generate_greedy_deterministic():
    cfg = get_smoke_config("qwen2-1.5b", n_layers=2, vocab=64)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    prompts = np.random.default_rng(1).integers(0, 64, (4, 8)).astype(np.int32)
    e1 = ServingEngine(cfg, params, ServeConfig(batch_slots=4, max_len=64))
    e2 = ServingEngine(cfg, params, ServeConfig(batch_slots=4, max_len=64))
    np.testing.assert_array_equal(e1.generate(prompts, 6),
                                  e2.generate(prompts, 6))


def test_continuous_batching_slots():
    cfg = get_smoke_config("smollm-135m", n_layers=2, vocab=64)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, ServeConfig(batch_slots=2, max_len=32))
    s0 = eng.submit([1, 2, 3])
    s1 = eng.submit([4, 5])
    assert {s0, s1} == {0, 1}
    assert eng.submit([9]) is None          # no free slot
    out = eng.step()
    assert set(out) == {0, 1}               # both slots decoded one token
    out2 = eng.step()
    assert set(out2) == {0, 1}


def test_interleaved_submit_leaves_other_slots_uncorrupted():
    """Regression for the submit cache-corruption bug: the old per-slot
    prefill ran full-batch decode with zero tokens, writing garbage K/V
    into every other live slot's cache at its current position and
    inflating its valid length. Admitting slot 1 mid-stream must leave
    slot 0's greedy decode byte-identical to an uninterrupted run."""
    cfg = get_smoke_config("smollm-135m", n_layers=2, vocab=64)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)

    def run(interleave: bool):
        eng = ServingEngine(cfg, params,
                            ServeConfig(batch_slots=2, max_len=32))
        assert eng.submit([1, 2, 3]) == 0
        outs = []
        for i in range(6):
            if interleave and i == 2:
                assert eng.submit([4, 5]) == 1
            outs.append(eng.step()[0])
        return outs

    assert run(False) == run(True)


def test_submit_masked_prefill_matches_generate_cache_state():
    """After submit, the admitted slot's cache length equals its prompt
    length and no other slot's length moved (the masked-prefill contract)."""
    import numpy as _np
    cfg = get_smoke_config("smollm-135m", n_layers=2, vocab=64)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, ServeConfig(batch_slots=3, max_len=32))
    eng.submit([7, 8, 9, 10])
    lens = _np.asarray(eng.caches["scan"]["len"])      # (n_layers, B)
    _np.testing.assert_array_equal(lens, [[4, 0, 0]] * lens.shape[0])
    eng.submit([5])
    lens = _np.asarray(eng.caches["scan"]["len"])
    _np.testing.assert_array_equal(lens, [[4, 1, 0]] * lens.shape[0])


def test_submit_step_matches_batched_generate():
    """Slot-mode decode must equal the batched generate() path on the same
    prompt token for token: submit() seeds the slot's pending token from the
    prefill argmax (no pseudo-BOS conditioning) and step() reports it before
    pipelining the next decode — no token of the stream is lost."""
    cfg = get_smoke_config("smollm-135m", n_layers=2, vocab=64)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    prompt = [3, 1, 4, 1, 5]
    e_batch = ServingEngine(cfg, params, ServeConfig(batch_slots=1,
                                                     max_len=32))
    want = e_batch.generate(np.asarray([prompt], np.int32), 5)[0].tolist()
    e_slot = ServingEngine(cfg, params, ServeConfig(batch_slots=2,
                                                    max_len=32))
    slot = e_slot.submit(prompt)
    got = [e_slot.step()[slot] for _ in range(5)]
    assert got == want
    assert e_slot.slot_out[slot] == want


def test_recycled_slot_restarts_clean():
    """A retired slot must be recycled from position 0 with its valid
    length zeroed — the new request's output equals a fresh engine's."""
    cfg = get_smoke_config("smollm-135m", n_layers=2, vocab=64)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    sc = ServeConfig(batch_slots=1, max_len=16)
    eng = ServingEngine(cfg, params, sc)
    eng.submit([9, 8, 7])
    while eng.slot_live[0]:          # decode to retirement at max_len
        eng.step()
    assert eng.slot_pos[0] >= sc.max_len - 1
    slot = eng.submit([1, 2, 3, 4])  # recycle
    assert slot == 0 and eng.slot_pos[0] == 4
    for _ in range(3):
        eng.step()
    fresh = ServingEngine(cfg, params, sc)
    fresh.submit([1, 2, 3, 4])
    for _ in range(3):
        fresh.step()
    assert eng.slot_out[0] == fresh.slot_out[0]


def test_retirement_flushes_final_token():
    """Regression: step() used to overwrite the freshly decoded slot_next
    when slot_pos hit max_len - 1, silently dropping the last token of
    every retired stream. The slot must drain — report the pending token —
    before retiring, so the slot stream is a strict prefix-match of an
    unbounded generate() stream of the same length."""
    cfg = get_smoke_config("smollm-135m", n_layers=2, vocab=64)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    prompt = [1, 2, 3]
    M = 8
    eng = ServingEngine(cfg, params, ServeConfig(batch_slots=1, max_len=M))
    eng.submit(prompt)
    toks = []
    while eng.slot_live[0]:
        toks.append(eng.step()[0])
    # prefill token + one decode per remaining cache slot (positions
    # S..M-1), the last of which is flushed by the drain round
    assert len(toks) == M - len(prompt) + 1, toks
    big = ServingEngine(cfg, params, ServeConfig(batch_slots=1, max_len=64))
    want = big.generate(np.asarray([prompt], np.int32),
                        len(toks))[0].tolist()
    assert toks == want           # nothing dropped, nothing reordered


def test_step_honors_temperature():
    """Regression: the continuous-batching path always did greedy argmax
    while generate() sampled. step()/submit() take an optional PRNG key and
    share generate()'s sampling rule."""
    cfg = get_smoke_config("smollm-135m", n_layers=2, vocab=64)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    sc = ServeConfig(batch_slots=1, max_len=32, temperature=2.0)

    def run(seed):
        eng = ServingEngine(cfg, params, sc)
        s = eng.submit([1, 2, 3], key=jax.random.PRNGKey(seed))
        return [eng.step(key=jax.random.PRNGKey(100 * seed + i))[s]
                for i in range(8)]

    sampled = run(1)
    assert sampled == run(1)            # deterministic under the same keys
    greedy_eng = ServingEngine(cfg, params,
                               ServeConfig(batch_slots=1, max_len=32))
    s = greedy_eng.submit([1, 2, 3])
    greedy = [greedy_eng.step()[s] for i in range(8)]
    assert sampled != greedy            # temperature actually applied
    # without a key the tempered engine still serves (greedy fallback)
    eng = ServingEngine(cfg, params, sc)
    s = eng.submit([1, 2, 3])
    assert [eng.step()[s] for i in range(8)] == greedy


def test_submit_rejects_multislot_ssm():
    """SSD/conv recurrent state carries no positions, so masked single-slot
    prefill cannot protect concurrent slots — multi-slot submit() must
    refuse rather than corrupt silently. With batch_slots=1 there is no
    other slot to corrupt, so the single-slot case still serves."""
    cfg = get_smoke_config("mamba2-1.3b", n_layers=2, vocab=64)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, ServeConfig(batch_slots=2, max_len=32))
    with pytest.raises(NotImplementedError, match="SSM"):
        eng.submit([1, 2, 3])
    solo = ServingEngine(cfg, params, ServeConfig(batch_slots=1, max_len=32))
    assert solo.submit([1, 2, 3]) == 0
    assert set(solo.step()) == {0}


def test_submit_rejects_oversized_prompt():
    cfg = get_smoke_config("smollm-135m", n_layers=1, vocab=64)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, ServeConfig(batch_slots=1, max_len=8))
    with pytest.raises(ValueError, match="prompt length"):
        eng.submit(list(range(8)))
    with pytest.raises(ValueError, match="prompt length"):
        eng.submit([])


def test_weight_dtype_implies_quantize_at_pack():
    """weight_dtype without pack_weights must still quantize once at engine
    build — quantizing inside the jitted decode would redo the O(K·N) work
    per token."""
    from repro.core.plan import QuantizedPackedWeight
    cfg = get_smoke_config("smollm-135m", n_layers=1, vocab=64)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, ServeConfig(
        batch_slots=1, max_len=16, weight_dtype="int8"))
    assert isinstance(eng.params["head"], QuantizedPackedWeight)


def test_quantized_packed_engine_matches_fp_greedy():
    """ServeConfig(pack_weights=True, weight_dtype="int8"): every projection
    weight becomes a resident QuantizedPackedWeight and greedy decode at
    temperature 0 tracks the unquantized engine.

    Two checks. The int8 engine's own stream (prefill, then decode against
    its cache) must equal the greedy chain of a full forward over its
    resident weights. Its agreement with the fp engine is top-1 per step
    under teacher forcing: both engines' weights score the fp engine's
    greedy stream, so every step compares the two argmaxes after the same
    history. Comparing two free-running streams would instead count every
    token after the first flipped near-tie (random smoke weights give
    margins of a few bf16 ulps) as a quantization miss. The floor is a 90%
    top-1 agreement rate (see docs/quant.md)."""
    from repro.core import api
    from repro.core.plan import GemmPolicy, QuantizedPackedWeight
    cfg = get_smoke_config("smollm-135m", n_layers=2, vocab=64)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    prompts = np.random.default_rng(3).integers(0, 64, (2, 6)).astype(np.int32)
    pol = GemmPolicy(backend="blockflow")
    e_fp = ServingEngine(cfg, params, ServeConfig(
        batch_slots=2, max_len=32, gemm=pol))
    e_q = ServingEngine(cfg, params, ServeConfig(
        batch_slots=2, max_len=32, gemm=pol, pack_weights=True,
        weight_dtype="int8"))
    assert isinstance(e_q.params["head"], QuantizedPackedWeight)
    assert isinstance(e_q.params["layers"]["attn"]["wq"],
                      QuantizedPackedWeight)
    o_fp = e_fp.generate(prompts, 8)
    o_q = e_q.generate(prompts, 8)
    n_prompt = prompts.shape[1]

    def argmaxes(eng, seq):
        with api.use_policy(eng.sc.policy()):
            logits, _, _ = T.forward(eng.params, cfg,
                                     {"tokens": jnp.asarray(seq)})
        return np.asarray(jnp.argmax(logits[:, n_prompt - 1:], -1))

    seq = prompts
    for _ in range(8):
        seq = np.concatenate([seq, argmaxes(e_q, seq)[:, -1:]], axis=1)
    np.testing.assert_array_equal(o_q, seq[:, n_prompt:])

    forced = np.concatenate([prompts, o_fp[:, :-1]], axis=1)
    # the teacher-forced fp forward reproduces the fp engine's stream
    np.testing.assert_array_equal(argmaxes(e_fp, forced), o_fp)
    agreement = float((argmaxes(e_q, forced) == o_fp).mean())
    assert agreement >= 0.9, f"top-1 agreement {agreement} < 0.9"


def test_fused_attention_token_streams_identical():
    """The acceptance gate: ServingEngine token streams — batched generate
    AND submit()/step() slot streams — must be identical under the fused
    flash-attention path and the unfused baseline."""
    cfg = get_smoke_config("smollm-135m", n_layers=2, vocab=64)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    prompt = [3, 1, 4, 1, 5]
    prompts = np.random.default_rng(5).integers(0, 64, (2, 6)).astype(np.int32)
    streams, gens = {}, {}
    for backend in ("unfused", "fused"):
        attn = AttentionPolicy(backend=backend, block_q=16, block_k=16)
        eng = ServingEngine(cfg, params, ServeConfig(
            batch_slots=2, max_len=32, attention=attn))
        slot = eng.submit(prompt)
        streams[backend] = [eng.step()[slot] for _ in range(6)]
        eng2 = ServingEngine(cfg, params, ServeConfig(
            batch_slots=2, max_len=32, attention=attn))
        gens[backend] = eng2.generate(prompts, 5)
    assert streams["unfused"] == streams["fused"]
    np.testing.assert_array_equal(gens["unfused"], gens["fused"])


def test_fused_interleaved_submit_leaves_other_slots_uncorrupted():
    """Interleaved submit()/step() with the fused attention path enabled:
    the masked position −1 rows must not write K/V through the fused
    kernel — admitting slot 1 mid-stream leaves slot 0's decode
    byte-identical to an uninterrupted run."""
    cfg = get_smoke_config("smollm-135m", n_layers=2, vocab=64)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    attn = AttentionPolicy(backend="fused", block_q=16, block_k=16)

    def run(interleave: bool):
        eng = ServingEngine(cfg, params, ServeConfig(
            batch_slots=2, max_len=32, attention=attn))
        assert eng.submit([1, 2, 3]) == 0
        outs = []
        for i in range(5):
            if interleave and i == 2:
                assert eng.submit([4, 5]) == 1
            outs.append(eng.step()[0])
        return outs

    assert run(False) == run(True)


def test_decode_prefill_logit_parity_fused_vs_unfused():
    """Same tokens through (a) one full prefill and (b) prefill + cached
    decode steps, on both attention backends: all four last-token logit
    vectors must agree within fp tolerance — decode-vs-prefill consistency
    of the offset/length-mask semantics, fused vs unfused."""
    from repro.serving.engine import make_decode_step, make_prefill_step
    cfg = get_smoke_config("smollm-135m", n_layers=2, vocab=64)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    toks = np.asarray([[7, 3, 11, 5, 2, 9]], np.int32)
    B, S = toks.shape
    logits = {}
    for backend in ("unfused", "fused"):
        attn = AttentionPolicy(backend=backend, block_q=16, block_k=16)
        prefill = make_prefill_step(cfg, attn=attn)
        decode = make_decode_step(cfg, attn=attn)
        # (a) one prefill over the whole sequence
        caches = T.init_caches(cfg, B, 32, jnp.bfloat16)
        full, _ = prefill(params, {
            "tokens": jnp.asarray(toks),
            "positions": jnp.broadcast_to(jnp.arange(S)[None], (B, S))},
            caches)
        # (b) prefill the prefix, then decode the rest token by token
        caches = T.init_caches(cfg, B, 32, jnp.bfloat16)
        cut = 3
        out, caches = prefill(params, {
            "tokens": jnp.asarray(toks[:, :cut]),
            "positions": jnp.broadcast_to(jnp.arange(cut)[None], (B, cut))},
            caches)
        for i in range(cut, S):
            out, caches = decode(params, jnp.asarray(toks[:, i:i + 1]),
                                 jnp.full((B, 1), i, jnp.int32), caches)
        logits[backend] = (np.asarray(full, np.float32),
                           np.asarray(out, np.float32))
    for a in logits["unfused"] + logits["fused"]:
        np.testing.assert_allclose(a, logits["unfused"][0],
                                   atol=5e-2, rtol=5e-2)


def test_packed_resident_weights_match_row_major():
    """ServeConfig(pack_weights=True) lays every projection weight out
    block-major once at engine build (the paper's Fig. 5 deployment shape);
    generation must match the row-major engine exactly under the same
    policy."""
    from repro.core.plan import GemmPolicy, PackedWeight
    cfg = get_smoke_config("smollm-135m", n_layers=2, vocab=64)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    pol = GemmPolicy(backend="blockflow", mode="dm")
    prompts = np.random.default_rng(2).integers(0, 64, (2, 6)).astype(np.int32)
    e_row = ServingEngine(cfg, params, ServeConfig(
        batch_slots=2, max_len=32, gemm=pol))
    e_packed = ServingEngine(cfg, params, ServeConfig(
        batch_slots=2, max_len=32, gemm=pol, pack_weights=True))
    assert isinstance(e_packed.params["head"], PackedWeight)
    np.testing.assert_array_equal(e_row.generate(prompts, 4),
                                  e_packed.generate(prompts, 4))


# ---------------------------------------------------------------------------
# Bucketed prefill (bounded recompiles) + generate() input validation
# ---------------------------------------------------------------------------

def test_generate_batch_mismatch_raises_with_shapes():
    """generate() must reject a prompts batch that doesn't match
    batch_slots with a ValueError naming both shapes (was a bare assert)."""
    cfg = get_smoke_config("smollm-135m", n_layers=1, vocab=64)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, ServeConfig(batch_slots=2, max_len=16))
    bad = np.zeros((3, 4), np.int32)
    with pytest.raises(ValueError) as ei:
        eng.generate(bad, 2)
    assert "(3, 4)" in str(ei.value) and "batch_slots=2" in str(ei.value)


def test_bucketed_prefill_stream_unchanged():
    """Satellite regression: submit() pads prompts to the next power-of-two
    width with position −1 columns (bounding per-length recompiles to
    log2(max_len) buckets); the token stream must be unchanged — identical
    to the unpadded batched generate() path — for lengths below, at, and
    above a bucket boundary."""
    from repro.serving.engine import _next_pow2
    assert [_next_pow2(n) for n in (1, 2, 3, 4, 5, 7, 8, 9)] == \
        [1, 2, 4, 4, 8, 8, 8, 16]
    cfg = get_smoke_config("smollm-135m", n_layers=2, vocab=64)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    for prompt in ([7, 3, 11], [3, 1, 4, 1, 5], [9, 8, 7, 6, 5, 4, 3, 2]):
        e_batch = ServingEngine(cfg, params,
                                ServeConfig(batch_slots=1, max_len=32))
        want = e_batch.generate(np.asarray([prompt], np.int32),
                                4)[0].tolist()
        e_slot = ServingEngine(cfg, params,
                               ServeConfig(batch_slots=2, max_len=32))
        slot = e_slot.submit(prompt)
        got = [e_slot.step()[slot] for _ in range(4)]
        assert got == want, (prompt, got, want)


# ---------------------------------------------------------------------------
# Paged KV-cache serving (docs/serving.md)
# ---------------------------------------------------------------------------

PAGED8 = AttentionPolicy(backend="paged", page_size=8, block_q=8)
FUSED8 = AttentionPolicy(backend="fused", block_q=8, block_k=8)


@pytest.fixture(scope="module")
def paged_setup():
    cfg = get_smoke_config("smollm-135m", n_layers=2, vocab=64)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_paged_token_streams_identical(paged_setup):
    """The acceptance gate: the paged engine's token streams — batched
    generate() AND submit()/step() — must be identical to the fused and
    unfused contiguous engines'."""
    cfg, params = paged_setup
    prompt = [3, 1, 4, 1, 5]
    prompts = np.random.default_rng(5).integers(0, 64, (2, 6)).astype(np.int32)
    streams, gens = {}, {}
    for name, attn in (("unfused", AttentionPolicy(backend="unfused")),
                       ("fused", FUSED8), ("paged", PAGED8)):
        eng = ServingEngine(cfg, params, ServeConfig(
            batch_slots=2, max_len=32, attention=attn))
        h = eng.submit(prompt)
        streams[name] = [eng.step()[h] for _ in range(6)]
        eng2 = ServingEngine(cfg, params, ServeConfig(
            batch_slots=2, max_len=32, attention=attn))
        gens[name] = eng2.generate(prompts, 5)
    assert streams["paged"] == streams["fused"] == streams["unfused"]
    np.testing.assert_array_equal(gens["paged"], gens["fused"])
    np.testing.assert_array_equal(gens["paged"], gens["unfused"])


def test_paged_interleaved_submit_leaves_other_slots_uncorrupted(paged_setup):
    """Admitting a second request mid-stream must not perturb the first:
    page-pool writes go through disjoint block tables, and the masked
    position −1 prefill rows must not write any page."""
    cfg, params = paged_setup

    def run(interleave: bool):
        eng = ServingEngine(cfg, params, ServeConfig(
            batch_slots=2, max_len=32, attention=PAGED8))
        r0 = eng.submit([1, 2, 3])
        outs = []
        for i in range(5):
            if interleave and i == 2:
                assert eng.submit([4, 5]) is not None
            outs.append(eng.step()[r0])
        return outs

    assert run(False) == run(True)


def test_paged_capacity_admission_is_page_bound(paged_setup):
    """The capacity acceptance gate: a request set whose summed
    max_len-padded footprint exceeds the pool budget is served
    *concurrently* — admission tracks pages (resident tokens), not
    slot-count × max_len."""
    cfg, params = paged_setup
    # 4 slots × max_len 32 = 128 padded tokens; pool = 8 pages × 8 = 64.
    sc = ServeConfig(batch_slots=4, max_len=32, attention=PAGED8,
                     cache_pages=8)
    eng = ServingEngine(cfg, params, sc)
    rids = [eng.submit([1 + i, 2, 3]) for i in range(4)]
    assert all(r is not None for r in rids)
    assert int(eng.slot_live.sum()) == 4           # all concurrently live
    padded = 4 * sc.max_len
    pool_tokens = eng.pool.n_pages * eng.pool.page_size
    assert padded > pool_tokens                    # genuinely oversubscribed
    # and the streams are still exact: compare two of them to solo runs
    for _ in range(4):
        eng.step()
    for i in (0, 3):
        solo = ServingEngine(cfg, params, sc)
        r = solo.submit([1 + i, 2, 3])
        want = [solo.step()[r] for _ in range(4)]
        assert eng.request_out[rids[i]] == want


def test_paged_preempt_resume_stream_identical(paged_setup):
    """Pool exhaustion must preempt the youngest request (pages freed,
    request parked) and later resume it with a token stream identical to
    an uninterrupted run — for every request in the workload."""
    cfg, params = paged_setup
    sc = ServeConfig(batch_slots=2, max_len=16, attention=PAGED8,
                     cache_pages=2)       # 2 pages of 8 = half the padded need
    eng = ServingEngine(cfg, params, sc)
    prompts = [[1, 2, 3], [4, 5, 6]]
    rids = [eng.submit(p) for p in prompts]
    assert all(r is not None for r in rids)
    for _ in range(60):
        eng.step()
        if not eng.slot_live.any() and not eng.wait:
            break
    assert eng.n_preemptions > 0                   # pressure actually hit
    assert not eng.slot_live.any() and not eng.wait
    eng.pool.check()
    assert eng.pool.free_pages == eng.pool.n_pages  # everything reclaimed
    for rid, p in zip(rids, prompts):
        solo = ServingEngine(cfg, params, sc)
        r = solo.submit(p)
        want = []
        while solo.slot_live.any():
            st = solo.step()
            if r in st:
                want.append(st[r])
        assert eng.request_out[rid] == want, (rid, p)


def test_paged_cancel_returns_pages(paged_setup):
    cfg, params = paged_setup
    eng = ServingEngine(cfg, params, ServeConfig(
        batch_slots=2, max_len=16, attention=PAGED8))
    r = eng.submit([1, 2, 3, 4, 5])
    assert eng.pool.pages_in_use > 0
    assert eng.cancel(r) is True
    assert eng.pool.free_pages == eng.pool.n_pages
    assert eng.cancel(r) is False                  # already gone
    eng.pool.check()


def test_paged_rejects_undersized_pool(paged_setup):
    """A pool that cannot back even one full-length request would wedge
    the wait queue forever — refuse at construction."""
    cfg, params = paged_setup
    with pytest.raises(ValueError, match="cache_pages"):
        ServingEngine(cfg, params, ServeConfig(
            batch_slots=2, max_len=32, attention=PAGED8, cache_pages=3))


def test_paged_generate_resets_pool(paged_setup):
    """Batched generate() owns the engine: it drops in-flight requests,
    reclaims every page, and two consecutive calls are deterministic."""
    cfg, params = paged_setup
    eng = ServingEngine(cfg, params, ServeConfig(
        batch_slots=2, max_len=32, attention=PAGED8))
    assert eng.submit([1, 2, 3]) is not None
    prompts = np.random.default_rng(2).integers(0, 64, (2, 4)).astype(np.int32)
    o1 = eng.generate(prompts, 4)
    o2 = eng.generate(prompts, 4)
    np.testing.assert_array_equal(o1, o2)
    eng.pool.check()


def test_paged_rejects_ssm_and_mla_families(paged_setup):
    from repro.models.transformer import init_paged_caches
    cfg_ssm = get_smoke_config("mamba2-1.3b", n_layers=2, vocab=64)
    with pytest.raises(NotImplementedError, match="recurrent"):
        init_paged_caches(cfg_ssm, 2, 8, 8, jnp.bfloat16)
    cfg_mla = get_smoke_config("deepseek-v2-236b", n_layers=2, vocab=64)
    with pytest.raises(NotImplementedError, match="MLA"):
        init_paged_caches(cfg_mla, 2, 8, 8, jnp.bfloat16)


def test_paged_generate_then_submit_no_page_leak(paged_setup):
    """Review regression: generate() pre-allocates horizon pages with no
    live slot owning them; a following submit() must not inherit-and-drop
    those tables (pages would leak unreleasable). The pool must stay
    exactly balanced across generate → submit → retire cycles."""
    cfg, params = paged_setup
    eng = ServingEngine(cfg, params, ServeConfig(
        batch_slots=2, max_len=32, attention=PAGED8))
    prompts = np.random.default_rng(4).integers(0, 64, (2, 4)).astype(np.int32)
    eng.generate(prompts, 8)
    assert eng.pool.free_pages == eng.pool.n_pages   # horizon pages returned
    r = eng.submit([1, 2, 3])
    assert r is not None
    for _ in range(3):
        eng.step()
    assert eng.cancel(r)
    eng.pool.check()
    assert eng.pool.free_pages == eng.pool.n_pages


def test_ssm_submit_stream_unaffected_by_bucketing():
    """Review regression: bucket padding columns carry position −1, a
    contract SSD/conv recurrent state is outside of (no positions) — a
    padded prefill would feed the pad tokens into the recurrence. SSM
    submit() (batch_slots=1) must prefill unpadded and match generate()
    token for token on a non-power-of-two prompt."""
    cfg = get_smoke_config("mamba2-1.3b", n_layers=2, vocab=64)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    prompt = [7, 3, 11]                        # len 3: would bucket to 4
    gen = ServingEngine(cfg, params, ServeConfig(batch_slots=1, max_len=32))
    want = gen.generate(np.asarray([prompt], np.int32), 4)[0].tolist()
    slot_eng = ServingEngine(cfg, params,
                             ServeConfig(batch_slots=1, max_len=32))
    s = slot_eng.submit(prompt)
    assert [slot_eng.step()[s] for _ in range(4)] == want


# ---------------------------------------------------------------------------
# Prefix cache: COW prompt-page sharing (docs/serving.md#prefix-cache)
# ---------------------------------------------------------------------------

def test_prefix_cache_streams_identical(paged_setup):
    """The golden gate: prefix cache ON must not change one token of any
    stream — shared-prefix prompts served with prefix_cache=True produce
    exactly the no-cache engine's streams, while actually sharing pages
    (hits recorded, fewer pages resident)."""
    cfg, params = paged_setup
    shared = list(range(1, 17))              # two full 8-token pages
    prompts = [shared + [40 + i, 50 + i] for i in range(3)]

    def streams(sc):
        eng = ServingEngine(cfg, params, sc)
        hs = [eng.submit(p) for p in prompts]
        assert all(h is not None for h in hs)
        for _ in range(5):
            eng.step()
        return [list(eng.request_out[h]) for h in hs], eng

    base = dict(batch_slots=4, max_len=32, attention=PAGED8, cache_pages=16)
    want, e0 = streams(ServeConfig(**base))
    got, e1 = streams(ServeConfig(**base, prefix_cache=True))
    assert got == want
    st = e1.stats()
    assert st["prefix_hits"] == 2            # requests 2 and 3 hit
    assert st["prefix_hit_tokens"] >= 2 * 16
    # sharing is real: the cached engine backs the same live set in fewer
    # pages than the private-copies engine
    assert e1.pool.pages_in_use < e0.pool.pages_in_use
    e1.pool.check()
    e1.prefix.check()


def test_prefix_cache_cow_divergence_isolated(paged_setup):
    """A request diverging INSIDE a cached page (COW fork) must match its
    solo stream, and its writes must not leak into the original holder's
    pages — both streams equal their uninterrupted solo runs."""
    cfg, params = paged_setup
    sc = ServeConfig(batch_slots=2, max_len=32, attention=PAGED8,
                     cache_pages=16, prefix_cache=True)
    eng = ServingEngine(cfg, params, sc)
    a = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10]      # one full page + tail
    b = a[:6] + [60, 61, 62, 63]             # diverges inside page 0
    ha = eng.submit(a)
    hb = eng.submit(b)                       # forks the partial match
    assert eng.prefix.cow_forks >= 1
    for _ in range(5):
        eng.step()
    for prompt, h in ((a, ha), (b, hb)):
        solo = ServingEngine(cfg, params, ServeConfig(
            batch_slots=2, max_len=32, attention=PAGED8, cache_pages=16))
        r = solo.submit(prompt)
        want = [solo.step()[r] for _ in range(5)]
        assert eng.request_out[h] == want, prompt
    eng.pool.check()


def test_prefix_preempt_resume_streams_identical(paged_setup):
    """Preempt/resume under prefix sharing + watermark eviction: every
    stream still equals its uninterrupted solo run, and the pool drains
    to exactly the cache-held pages (all reclaimable)."""
    cfg, params = paged_setup
    # 9-token prompts share page 0 → 3 pages admit both; decode growth to
    # max_len 24 needs 3 pages each (5 total shared) > the 4-page pool
    sc = ServeConfig(batch_slots=2, max_len=24, attention=PAGED8,
                     cache_pages=4, prefix_cache=True, prefix_watermark=1)
    eng = ServingEngine(cfg, params, sc)
    prompts = [[1, 2, 3, 4, 5, 6, 7, 8, 9], [1, 2, 3, 4, 5, 6, 7, 8, 11]]
    rids = [eng.submit(p) for p in prompts]
    assert all(r is not None for r in rids)
    for _ in range(80):
        eng.step()
        if not eng.slot_live.any() and not eng.wait:
            break
    assert eng.n_preemptions > 0             # pressure actually hit
    assert not eng.slot_live.any() and not eng.wait
    eng.pool.check()
    eng.prefix.check()
    # every non-free page is a cold cache entry, reclaimable on demand
    assert eng.pool.pages_in_use == eng.prefix.reclaimable()
    for rid, p in zip(rids, prompts):
        solo = ServingEngine(cfg, params, ServeConfig(
            batch_slots=2, max_len=24, attention=PAGED8, cache_pages=4))
        r = solo.submit(p)
        want = []
        while solo.slot_live.any():
            st = solo.step()
            if r in st:
                want.append(st[r])
        assert eng.request_out[rid] == want, (rid, p)


def test_prefix_watermark_restores_free_pages(paged_setup):
    """ServeConfig.prefix_watermark: step() evicts cold cached entries
    until that many pages are free — retired prefixes don't squat the
    pool below the floor."""
    cfg, params = paged_setup
    sc = ServeConfig(batch_slots=2, max_len=32, attention=PAGED8,
                     cache_pages=8, prefix_cache=True, prefix_watermark=7)
    eng = ServingEngine(cfg, params, sc)
    r = eng.submit(list(range(1, 18)))       # 17 tokens → 3 pages, 2 cached
    eng.cancel(r)                            # retire: cache refs remain
    assert eng.pool.free_pages == 6          # 2 cold cached pages squat
    eng.step()                               # watermark sweep runs
    assert eng.pool.free_pages >= 7
    assert eng.stats()["prefix_evictions"] >= 1


def test_prefix_cache_requires_paged_backend(paged_setup):
    cfg, params = paged_setup
    with pytest.raises(ValueError, match="prefix_cache"):
        ServingEngine(cfg, params, ServeConfig(
            batch_slots=2, max_len=32, prefix_cache=True))


def test_engine_stats_dict(paged_setup):
    """ServingEngine.stats() (satellite): one observability dict on both
    backends — counters the launcher prints and the sweep records."""
    cfg, params = paged_setup
    core = {"tick", "live_requests", "waiting_requests", "n_preemptions",
            "prefill_tokens", "decode_tokens"}
    eng = ServingEngine(cfg, params, ServeConfig(batch_slots=2, max_len=32))
    eng.submit([1, 2, 3])
    eng.step()
    st = eng.stats()
    assert core <= set(st)
    assert st["prefill_tokens"] == 3 and st["decode_tokens"] == 1
    assert st["live_requests"] == 1

    eng = ServingEngine(cfg, params, ServeConfig(
        batch_slots=2, max_len=32, attention=PAGED8, cache_pages=8,
        prefix_cache=True))
    eng.submit(list(range(1, 10)))
    eng.step()
    st = eng.stats()
    assert core <= set(st)
    assert {"pool_pages", "pool_free_pages", "pool_pages_in_use",
            "pool_high_water", "prefix_hits", "prefix_hit_rate"} <= set(st)
    assert st["pool_pages"] == 8
    assert st["pool_high_water"] >= st["pool_pages_in_use"] > 0


def test_paged_generate_does_not_accumulate_cache_lens(paged_setup):
    """Review regression: generate() never advances slot_pos, so the paged
    reset must zero cache lens unconditionally — otherwise kv_valid_len
    inflates past the block-table-backed range on every generate() call
    (stale-garbage keys under non-causal attention, dead block-skip under
    causal)."""
    cfg, params = paged_setup
    eng = ServingEngine(cfg, params, ServeConfig(
        batch_slots=2, max_len=32, attention=PAGED8))
    prompts = np.random.default_rng(6).integers(0, 64, (2, 4)).astype(np.int32)
    o1 = eng.generate(prompts, 4)
    lens1 = np.asarray(eng.caches["scan"]["len"]).copy()
    o2 = eng.generate(prompts, 4)
    lens2 = np.asarray(eng.caches["scan"]["len"])
    np.testing.assert_array_equal(lens1, lens2)      # no accumulation
    np.testing.assert_array_equal(lens2, 0)          # reset on completion
    np.testing.assert_array_equal(o1, o2)            # hence deterministic
    assert eng.pool.free_pages == eng.pool.n_pages


# ---------------------------------------------------------------------------
# Quantized int8 KV pages (ServeConfig.kv_dtype — docs/quant.md#kv-pages)
# ---------------------------------------------------------------------------

PAGED8_INT8 = dict(attention=PAGED8, kv_dtype="int8")


def test_kv_int8_streams_self_consistent_and_greedy_match(paged_setup):
    """The int8 engine's submit()/step() streams must equal its own
    batched generate() (shared write path, shared kernel), and — on this
    smoke model, where quantization noise stays under every argmax
    margin — the greedy streams also match the fp paged engine's."""
    cfg, params = paged_setup
    prompt = [3, 1, 4, 1, 5]
    eng = ServingEngine(cfg, params, ServeConfig(
        batch_slots=2, max_len=32, **PAGED8_INT8))
    h = eng.submit(prompt)
    stream = [eng.step()[h] for _ in range(6)]
    eng2 = ServingEngine(cfg, params, ServeConfig(
        batch_slots=2, max_len=32, **PAGED8_INT8))
    batch = np.asarray([prompt, prompt], np.int32)
    gen = eng2.generate(batch, 6)
    assert stream == list(np.asarray(gen)[0])
    fp = ServingEngine(cfg, params, ServeConfig(
        batch_slots=2, max_len=32, attention=PAGED8))
    np.testing.assert_array_equal(np.asarray(fp.generate(batch, 6)),
                                  np.asarray(gen))


def test_kv_int8_preempt_resume_stream_identical(paged_setup):
    """Preempt/resume exactness under the quantized pool: resume
    re-prefills in bulk what was written token-at-a-time before the
    preemption, so this passes ONLY because the frozen-first-row page
    scales make the int8 payload a pure function of logical content
    (tests/test_kv_quant.py proves that invariant bitwise)."""
    cfg, params = paged_setup
    sc = ServeConfig(batch_slots=2, max_len=16, cache_pages=2,
                     **PAGED8_INT8)
    eng = ServingEngine(cfg, params, sc)
    prompts = [[1, 2, 3], [4, 5, 6]]
    rids = [eng.submit(p) for p in prompts]
    assert all(r is not None for r in rids)
    for _ in range(60):
        eng.step()
        if not eng.slot_live.any() and not eng.wait:
            break
    assert eng.n_preemptions > 0                   # pressure actually hit
    assert not eng.slot_live.any() and not eng.wait
    eng.pool.check()
    assert eng.pool.free_pages == eng.pool.n_pages
    for rid, p in zip(rids, prompts):
        solo = ServingEngine(cfg, params, sc)
        r = solo.submit(p)
        want = []
        while solo.slot_live.any():
            st = solo.step()
            if r in st:
                want.append(st[r])
        assert eng.request_out[rid] == want, (rid, p)


def test_kv_int8_prefix_cow_streams_identical(paged_setup):
    """Prefix-cache COW over quantized pages: _copy_page must clone the
    int8 slabs AND the scale rows, so a fork diverging inside a cached
    page still matches its solo stream exactly."""
    cfg, params = paged_setup
    sc = ServeConfig(batch_slots=2, max_len=32, cache_pages=16,
                     prefix_cache=True, **PAGED8_INT8)
    eng = ServingEngine(cfg, params, sc)
    a = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10]      # one full page + tail
    b = a[:6] + [60, 61, 62, 63]             # diverges inside page 0
    ha = eng.submit(a)
    hb = eng.submit(b)                       # forks the partial match
    assert eng.prefix.cow_forks >= 1
    for _ in range(5):
        eng.step()
    for prompt, h in ((a, ha), (b, hb)):
        solo = ServingEngine(cfg, params, ServeConfig(
            batch_slots=2, max_len=32, cache_pages=16, **PAGED8_INT8))
        r = solo.submit(prompt)
        want = [solo.step()[r] for _ in range(5)]
        assert eng.request_out[h] == want, prompt
    eng.pool.check()
    eng.prefix.check()


def test_kv_int8_stats_and_pool_bytes(paged_setup):
    """stats() reports the pool's byte economics; an int8 page must cost
    ≤ 1/1.8 of the bf16 page (2x payload minus the fp32 scale rows) —
    the per-page form of the ≥1.8x capacity gate benchmarks/
    serving_sweep.py::sweep_kv measures end to end."""
    cfg, params = paged_setup
    base = dict(batch_slots=2, max_len=32, cache_pages=8,
                cache_dtype="bfloat16")
    fp = ServingEngine(cfg, params, ServeConfig(**base, attention=PAGED8))
    q8 = ServingEngine(cfg, params, ServeConfig(**base, **PAGED8_INT8))
    st = q8.stats()
    assert st["kv_dtype"] == "int8"
    assert st["kv_page_bytes"] == q8.kv_page_bytes()
    assert st["kv_pool_bytes"] == 8 * st["kv_page_bytes"]
    assert fp.stats()["kv_dtype"] == "bfloat16"
    assert 1.8 * q8.kv_page_bytes() <= fp.kv_page_bytes()
    q8.submit([1, 2, 3])
    st = q8.stats()
    assert st["kv_bytes_in_use"] == \
        st["kv_page_bytes"] * st["pool_pages_in_use"] > 0
    # the pools really are int8 + fp32 scales
    scan = q8.caches["scan"]
    assert scan["kp"].dtype == jnp.int8 and scan["vp"].dtype == jnp.int8
    assert scan["k_scale"].dtype == jnp.float32


def test_kv_dtype_requires_paged_backend(paged_setup):
    """kv_dtype on a dense backend must refuse at construction — dense
    caches have no pages to hang scales off."""
    cfg, params = paged_setup
    with pytest.raises(ValueError, match="kv_dtype"):
        ServingEngine(cfg, params, ServeConfig(
            batch_slots=2, max_len=32, kv_dtype="int8"))
    with pytest.raises(ValueError, match="kv_dtype"):
        ServingEngine(cfg, params, ServeConfig(
            batch_slots=2, max_len=32, attention=FUSED8, kv_dtype="int8"))


def test_misbehaving_scheduler_victim_raises_descriptive_error(paged_setup):
    """Satellite bugfix: a Scheduler.victim subclass returning a rid that
    is not live used to surface as a bare StopIteration out of
    _grow_pages_for_decode's next(); it must raise a RuntimeError naming
    the offending rid and the live set."""
    from repro.serving.scheduler import Scheduler

    class Misbehaving(Scheduler):
        def victim(self, live):
            return 999_999               # no such request

    cfg, params = paged_setup
    sc = ServeConfig(batch_slots=2, max_len=16, attention=PAGED8,
                     cache_pages=2, scheduler=Misbehaving())
    eng = ServingEngine(cfg, params, sc)
    assert eng.submit([1, 2, 3]) is not None
    assert eng.submit([4, 5, 6]) is not None
    with pytest.raises(RuntimeError, match=r"999999.*not a live request"):
        for _ in range(30):              # decode until the pool runs dry
            eng.step()


def test_preempt_of_draining_slot_stream_identical(paged_setup):
    """Satellite audit: preempting a slot whose FINAL token is pending
    (slot_drain set, cache full) must still yield a token-identical
    stream after resume — the drain flag is recomputed on resume and the
    parked pending token is reported, not re-sampled."""
    cfg, params = paged_setup
    sc = ServeConfig(batch_slots=1, max_len=16, attention=PAGED8)
    solo = ServingEngine(cfg, params, sc)
    r = solo.submit([1, 2, 3])
    want = []
    while solo.slot_live.any():
        st = solo.step()
        if r in st:
            want.append(st[r])

    eng = ServingEngine(cfg, params, sc)
    r2 = eng.submit([1, 2, 3])
    got = []
    preempted = False
    for _ in range(60):
        if eng.slot_drain[0] and not preempted:
            eng._preempt(0)              # forced: drain slots are normally
            preempted = True             # spared (no page growth needed)
        st = eng.step()
        if r2 in st:
            got.append(st[r2])
        if not eng.slot_live.any() and not eng.wait:
            break
    assert preempted                     # the drain state was actually hit
    assert got == want
    eng.pool.check()
    assert eng.pool.free_pages == eng.pool.n_pages


def test_bursty_cancel_during_preempt_resume_leaks_no_pages(paged_setup):
    """Satellite: cancel() storms while requests bounce between slots and
    the wait queue (tight pool → constant preempt/resume) must return the
    pool to its baseline free count — no page leaks on any cancel path."""
    cfg, params = paged_setup
    sc = ServeConfig(batch_slots=2, max_len=16, attention=PAGED8,
                     cache_pages=3)
    eng = ServingEngine(cfg, params, sc)
    rng = np.random.default_rng(11)
    live_rids = []
    for i in range(40):
        if len(live_rids) < 4:
            r = eng.submit([int(t) for t in
                            rng.integers(0, 64, int(rng.integers(2, 9)))])
            if r is not None:
                live_rids.append(r)
        eng.step()
        if live_rids and i % 3 == 2:     # bursty cancels: live AND waiting
            burst = [live_rids.pop(rng.integers(len(live_rids)))
                     for _ in range(min(2, len(live_rids)))]
            for r in burst:
                eng.cancel(r)
        eng.pool.check()
    assert eng.n_preemptions > 0         # churn actually happened
    for r in live_rids:
        eng.cancel(r)
    for _ in range(40):                  # drain whatever remains
        if not eng.slot_live.any() and not eng.wait:
            break
        eng.step()
        for h in list(eng.request_out):
            eng.cancel(h)
    eng.pool.check()
    assert eng.pool.free_pages == eng.pool.n_pages
