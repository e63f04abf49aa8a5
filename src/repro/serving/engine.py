"""Batched serving engine: prefill + decode steps over the model's caches.

``prefill_step``/``decode_step`` are the functions the dry-run lowers for the
``prefill_*`` / ``decode_*`` / ``long_*`` shape cells. The engine adds a
continuous-batching front end: a slot-based scheduler that admits queued
requests into free batch slots between decode iterations (the vLLM-style
pattern, reduced to its core).

GEMM execution is governed by a GemmPolicy (ServeConfig.gemm); with
``pack_weights=True`` every projection weight is laid out block-major once
at engine construction (api.pack_model_weights) and stays resident — the
paper's Fig. 5 deployment shape, where serving never re-lays-out a weight.
``weight_dtype="int8"`` additionally quantizes at pack: weights live as
int8 blocks + per-channel scales and GEMMs run the W8A8 route
(core/quant.py, docs/quant.md). Attention execution is governed the same
way by ServeConfig.attention (an AttentionPolicy): ``fused`` streams K/V
blocks through the offset-aware flash kernel for both prefill and decode,
``unfused`` keeps the paper's host-softmax split (docs/attention.md), and
``paged`` swaps the contiguous ``(batch_slots, max_len)`` KV slab for a
**page pool** with per-request block tables (serving/kv_pool.py,
kernels/paged_attention.py, docs/serving.md). In paged mode admission is
**page-bound** instead of slot-bound: a request is admitted while free
pages cover its prompt, decode steps allocate pages on demand, retirement
returns them, and when the pool runs dry a live request is preempted —
spilled to a wait queue and resumed later with a token stream identical to
an uninterrupted run. ``submit``/``step`` then key their results by
*request id* (the handle submit returns), since a request may migrate
across slots.

Scheduling *policy* — resume order, preemption victims, priority
admission, chunked prefill — lives in serving/scheduler.py
(``ServeConfig.scheduler``; the default reproduces the PR 4/5 FIFO +
youngest-preemption choreography exactly). With ``prefix_cache=True``
the paged engine additionally shares full prompt-prefix KV pages across
requests through a copy-on-write radix cache (serving/prefix_cache.py):
submit looks the prompt up, borrows every cached full page (pool ref
counts), forks the first divergent page, and prefills only the uncached
tail; finished prefills index their prompt pages for later requests, and
cold entries evict by LRU when the pool runs low.

Slot admission uses *masked* prefill/decode: batch rows — and, for the
power-of-two **bucketed prefill** that bounds per-prompt-length recompiles,
padding columns — at position -1 neither write the KV cache nor advance
the valid length, so one slot's prefill cannot corrupt concurrent slots
(SSD/conv caches don't carry positions and are outside this masking
contract).
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import api
from repro.core.plan import AttentionPolicy, GemmPolicy, ShardingPolicy
from repro.distributed import tp as TP
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.obs import NULL_OBS, Observability, RequestTrace, install_gc_span
from repro.obs.metrics import TIME_BUCKETS_S, json_scalars
from repro.serving.kv_pool import BlockTable, PagePool
from repro.serving.prefix_cache import PrefixCache
from repro.serving.scheduler import RequestView, Scheduler

PAGED_BACKENDS = ("paged",)


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length() if n > 1 else 1


@dataclasses.dataclass
class ServeConfig:
    batch_slots: int = 8
    max_len: int = 1024
    temperature: float = 0.0     # 0 → greedy
    cache_dtype: str = "bfloat16"
    gemm: Optional[GemmPolicy] = None   # None → the ambient/default policy
    pack_weights: bool = False          # resident block-major weights
    weight_dtype: Optional[str] = None  # "int8" → quantized W8A8 GEMM route
    attention: Optional[AttentionPolicy] = None  # None → ambient/default
    # (AttentionPolicy(backend="fused") routes prefill AND decode through
    # the offset-aware flash kernel; backend="paged" additionally pages the
    # KV cache — docs/attention.md, docs/serving.md)
    kv_dtype: Optional[str] = None  # paged backends only: "int8" → int8 KV
    # pages with per-page-per-head fp32 scales, quantized at write time and
    # dequantized inside the paged kernel — half the pool bytes per
    # resident token (docs/quant.md#kv-pages).
    cache_pages: Optional[int] = None
    # paged backends only: total pages in the KV pool. None → the
    # contiguous-equivalent budget batch_slots * ceil(max_len / page_size);
    # smaller values make admission page-bound (the memory-oversubscription
    # regime the paged subsystem exists for).
    mesh: Optional[object] = None       # jax.sharding.Mesh → TP serving:
    # prefill/decode run under a repro.distributed.tp context — QKV/up
    # column-parallel, out/down row-parallel (psum), attention heads and
    # the per-shard paged KV pools split over the mesh's model axis
    # (docs/serving.md). None → single-device serving, unchanged.
    sharding: Optional[ShardingPolicy] = None  # axis names + rule overrides
    # for the mesh; None → ShardingPolicy() (("data", "model") axes).
    prefix_cache: bool = False
    # paged backends only: share full prompt-prefix KV pages across
    # requests through a copy-on-write radix cache
    # (serving/prefix_cache.py, docs/serving.md#prefix-cache).
    prefix_watermark: int = 0
    # with prefix_cache: evict cold cached entries at step() start until at
    # least this many pool pages are free. 0 → evict only on demand, when
    # an admission would otherwise fall short of pages.
    scheduler: Optional[Scheduler] = None
    # scheduling policy (serving/scheduler.py): resume order, preemption
    # victims, priority admission, chunked prefill. None → Scheduler(),
    # the FIFO-within-priority default that reproduces the PR 4/5
    # choreography (oldest resumes first, youngest preempts first,
    # whole-prompt prefill).
    spec: Optional[object] = None
    # speculative decoding (serving/spec_decode.py): a Drafter proposing
    # up to ``spec.k`` continuation tokens per request per step; the
    # engine verifies all of them in ONE masked forward (Sq = 1 + k at
    # each slot's offset), keeps the longest target-agreeing prefix plus
    # the bonus token, and rolls rejected tokens back (valid-length
    # reset; paged: BlockTable.truncate). Greedy streams stay
    # token-identical to spec=None; step() returns {handle: [tokens]}
    # bursts instead of single tokens. Greedy only (temperature == 0) —
    # docs/serving.md#speculative-decoding.
    obs: Observability = NULL_OBS
    # observability (repro/obs, docs/observability.md): metrics registry +
    # trace recorder + per-request lifecycle records. The default NULL_OBS
    # is fully disabled — hot paths pay one attribute load + branch and
    # record/allocate nothing. Pass Observability() to collect; sharing
    # one instance across engines merges their metrics (process-wide
    # registry semantics) and interleaves their trace tracks.

    def policy(self) -> Optional[GemmPolicy]:
        """The effective GemmPolicy: ``gemm`` with ``weight_dtype`` folded
        in. With ``pack_weights=True`` this makes every projection weight a
        resident QuantizedPackedWeight (quantize-at-pack)."""
        if self.weight_dtype is None:
            return self.gemm
        return dataclasses.replace(self.gemm or GemmPolicy(),
                                   weight_dtype=self.weight_dtype)

    def attn_policy(self) -> Optional[AttentionPolicy]:
        """The effective AttentionPolicy: ``attention`` with ``kv_dtype``
        folded in (mirrors :meth:`policy`'s weight_dtype folding)."""
        if self.kv_dtype is None:
            return self.attention
        return dataclasses.replace(self.attention or AttentionPolicy(),
                                   kv_dtype=self.kv_dtype)

    def paged(self) -> bool:
        return (self.attention is not None
                and self.attention.resolved_backend() in PAGED_BACKENDS)


@dataclasses.dataclass
class _Waiting:
    """A preempted (or re-queued) request parked off-device: everything
    needed to rebuild its cache by re-prefilling ``prompt + out`` and
    continue the stream exactly where it stopped. ``next_tok`` is None
    only for a request preempted *mid-chunked-prefill* — no token was
    sampled yet; ``key`` then re-seeds the first sample on resume so the
    stream is unchanged under any temperature."""
    rid: int
    prompt: List[int]            # the ORIGINAL prompt, never rewritten
    out: List[int]               # reported tokens — the live stream list
    next_tok: Optional[int]      # sampled but not yet reported/written
    key: Optional[jax.Array] = None
    priority: int = 0
    deadline: Optional[float] = None
    arrival: int = 0


def _policy_scope(policy: Optional[GemmPolicy],
                  attn: Optional[AttentionPolicy] = None,
                  tpctx: Optional[TP.TPContext] = None):
    stack = contextlib.ExitStack()
    if policy is not None:
        stack.enter_context(api.use_policy(policy))
    if attn is not None:
        stack.enter_context(api.use_attention_policy(attn))
    if tpctx is not None:
        stack.enter_context(TP.use_tp(tpctx))
    return stack


def make_prefill_step(cfg: ModelConfig, policy: Optional[GemmPolicy] = None,
                      attn: Optional[AttentionPolicy] = None,
                      tpctx: Optional[TP.TPContext] = None):
    """(params, batch, caches) → (last_logits, caches). Processes the full
    prompt with causal self-attention while writing the caches.

    batch may carry ``last_cols`` (B,) — the column holding each row's last
    *real* token under bucketed (position −1 padded) prefill — and
    ``block_tables`` for paged caches; absent both, this is the plain
    dense prefill returning the final column's logits. ``tpctx`` runs the
    forward tensor-parallel over its mesh (repro/distributed/tp.py)."""
    def prefill_step(params, batch, caches):
        with _policy_scope(policy, attn, tpctx):
            logits, caches, _ = T.forward(params, cfg, batch, caches=caches,
                                          remat=False)
        last = batch.get("last_cols")
        if last is None:
            return logits[:, -1], caches
        picked = jnp.take_along_axis(logits, last[:, None, None], axis=1)
        return picked[:, 0], caches
    return prefill_step


def make_verify_step(cfg: ModelConfig, policy: Optional[GemmPolicy] = None,
                     attn: Optional[AttentionPolicy] = None,
                     tpctx: Optional[TP.TPContext] = None):
    """(params, batch{tokens (B,Sq), positions (B,Sq)[, block_tables]},
    caches) → (greedy (B,Sq) int32, caches). The speculative-verification
    forward: Sq = 1 + k tokens per row — the pending token plus up to k
    drafts — at each slot's current offset, under the same masked-write
    contract as chunked prefill (position −1 rows neither write KV nor
    bump the valid length; the offset-aware kernels already causal-mask
    Sq > 1 at arbitrary offsets, so no new kernel is needed). Unlike
    make_prefill_step this returns the argmax at EVERY query position:
    column i is the target's greedy choice after consuming the row's
    tokens [0..i], which is exactly what acceptance compares drafts
    against. Greedy-only by design — distribution-preserving rejection
    sampling for temperature > 0 is out of scope here."""
    def verify_step(params, batch, caches):
        with _policy_scope(policy, attn, tpctx):
            logits, caches, _ = T.forward(params, cfg, batch, caches=caches,
                                          remat=False)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), caches
    return verify_step


def make_decode_step(cfg: ModelConfig, policy: Optional[GemmPolicy] = None,
                     attn: Optional[AttentionPolicy] = None,
                     tpctx: Optional[TP.TPContext] = None):
    """(params, tokens(B,1), positions(B,1), caches[, block_tables]) →
    (logits, caches). ``block_tables`` is None for contiguous caches."""
    def decode_step(params, tokens, positions, caches, block_tables=None):
        batch = {"tokens": tokens, "positions": positions}
        if block_tables is not None:
            batch["block_tables"] = block_tables
        with _policy_scope(policy, attn, tpctx):
            logits, caches, _ = T.forward(params, cfg, batch, caches=caches,
                                          remat=False)
        return logits[:, -1], caches
    return decode_step


class ServingEngine:
    """Greedy/temperature sampling with slot-based continuous batching.

    With a paged attention policy (``ServeConfig.attention`` backend
    "paged") the engine runs **memory-bound continuous
    batching**: submit() returns a *request id*, admission holds while free
    pages cover the prompt, decode grows block tables on demand, and pool
    exhaustion preempts a scheduler-chosen victim into a wait queue from
    which step() resumes it once pages and a slot free up —
    docs/serving.md walks the full lifecycle.

    ``ServeConfig.prefix_cache`` adds copy-on-write prompt-prefix sharing
    over the same pool (serving/prefix_cache.py); ``ServeConfig.scheduler``
    swaps the scheduling policy — chunked prefill, priorities, SLO
    deadlines (serving/scheduler.py). Both default OFF/FIFO, reproducing
    the PR 4/5 engine token-for-token.

    With ``ServeConfig.mesh`` the same engine serves **tensor-parallel**:
    prefill/decode run under a repro/distributed/tp.py context (shard_map'd
    column/row-parallel GEMMs, head-sharded attention, per-shard paged KV
    pools), with params and caches placed mesh-resident at construction.
    Host-side scheduling — admission, page accounting, preemption, the
    prefix cache — is unchanged (pages are logical; every shard mirrors
    the allocation over its head slice), so TP token streams are identical
    to single-device streams (tests/test_tp_serving.py).
    """

    def __init__(self, cfg: ModelConfig, params, sc: ServeConfig,
                 axes=None):
        pol = sc.policy()
        self.tp = None
        if sc.mesh is not None:
            if sc.pack_weights or sc.weight_dtype is not None:
                raise NotImplementedError(
                    "TP serving (ServeConfig.mesh) does not yet cover "
                    "resident packed/quantized weights — block-major "
                    "PackedWeight pytrees would need per-shard re-packing; "
                    "drop pack_weights/weight_dtype or the mesh")
            self.tp = TP.make_context(sc.mesh, sc.sharding, cfg)
            if axes is None:
                # placement needs the logical-axis tree; derived by
                # abstract tracing (no weight materialization) when the
                # caller didn't keep init_model's second return
                axes = T.model_axes(cfg)
            params = TP.shard_params(params, axes, self.tp)
        # Quantizing per call inside the jitted forward would redo the
        # O(K·N) weight quantization on every decode token; weights are
        # static across calls, so weight_dtype always quantizes-at-pack.
        if sc.pack_weights or sc.weight_dtype is not None:
            params = api.pack_model_weights(params, pol)
        self.cfg, self.params, self.sc = cfg, params, sc
        attn = sc.attn_policy()   # validates kv_dtype via AttentionPolicy
        self.decode = jax.jit(make_decode_step(cfg, pol, attn, self.tp))
        self.prefill = jax.jit(make_prefill_step(cfg, pol, attn, self.tp))
        self.spec = sc.spec
        if self.spec is not None:
            if sc.temperature > 0:
                raise ValueError(
                    "ServeConfig.spec requires greedy sampling "
                    "(temperature == 0): acceptance compares drafts "
                    "against the target's argmax, and the rollback path "
                    "implements no distribution-preserving rejection "
                    "sampling (docs/serving.md#speculative-decoding)")
            if cfg.family in ("ssm", "hybrid"):
                raise ValueError(
                    "ServeConfig.spec requires position-masked multi-"
                    "token cache writes; SSD/conv recurrent state has no "
                    "positions to mask or roll back")
            if int(getattr(self.spec, "k", 0)) < 1:
                raise ValueError(
                    f"ServeConfig.spec drafter needs k >= 1 "
                    f"(got {getattr(self.spec, 'k', None)!r}); see "
                    f"serving/spec_decode.py")
            self.verify = jax.jit(make_verify_step(cfg, pol, attn, self.tp))
        B = sc.batch_slots
        self.paged = sc.paged()
        if sc.kv_dtype is not None and not self.paged:
            raise ValueError(
                "ServeConfig.kv_dtype requires a paged attention policy "
                "(backend 'paged') — only the page pool "
                "stores quantized K/V (docs/quant.md#kv-pages)")
        self.scheduler = sc.scheduler if sc.scheduler is not None \
            else Scheduler()
        # Observability: instruments are registered once here (the slow
        # phase) and held as direct references — the per-token paths only
        # ever do `if obs.enabled:` plus an int add. With NULL_OBS every
        # instrument is the shared no-op and nothing is recorded; the
        # engine's spans (obs.trace.span) still reach a profiler capture.
        self.obs = sc.obs if sc.obs is not None else NULL_OBS
        obs = self.obs
        install_gc_span()
        if obs.enabled:
            m = obs.metrics
            self._m_prefill_tokens = m.counter("engine_tokens_total",
                                               stage="prefill")
            self._m_decode_tokens = m.counter("engine_tokens_total",
                                              stage="decode")
            self._m_admissions = m.counter("engine_admissions_total",
                                           kind="fresh")
            self._m_resumes = m.counter("engine_admissions_total",
                                        kind="resume")
            self._m_preemptions = m.counter("engine_preemptions_total")
            self._m_retired = m.counter("engine_retired_total")
            self._m_cancelled = m.counter("engine_cancelled_total")
            self._m_live = m.gauge("engine_live_requests")
            self._m_waiting = m.gauge("engine_waiting_requests")
            self._h_ttft = m.histogram("request_ttft_s", TIME_BUCKETS_S)
            self._h_itl = m.histogram("request_itl_s", TIME_BUCKETS_S)
            if self.spec is not None:
                self._m_spec_accepted = m.counter("spec_tokens_total",
                                                  verdict="accepted")
                self._m_spec_rejected = m.counter("spec_tokens_total",
                                                  verdict="rejected")
                self._m_spec_rollback = m.counter(
                    "spec_rollback_pages_total")
                self._h_spec_accept = m.histogram(
                    "spec_acceptance_rate",
                    buckets=(0.125, 0.25, 0.375, 0.5,
                             0.625, 0.75, 0.875, 1.0))
            self.scheduler.bind_metrics(m)
        # handle → lifecycle record (RequestTrace), built only when obs is
        # enabled; persists past retirement so finished streams stay
        # readable via request_trace(). Paged handles (request ids) are
        # unique per engine; contiguous handles are slot ids, so a slot's
        # next request replaces the previous record.
        self.request_traces: Dict[int, RequestTrace] = {}
        self.prefix: Optional[PrefixCache] = None
        if sc.prefix_cache and not self.paged:
            raise ValueError(
                "ServeConfig.prefix_cache requires a paged attention "
                "policy (backend 'paged') — prefix "
                "sharing aliases pool pages through block tables, which "
                "the contiguous per-slot KV slab has none of")
        if self.paged:
            ps = sc.attention.page_size
            self.n_blocks = -(-sc.max_len // ps)
            n_pages = (sc.cache_pages if sc.cache_pages is not None
                       else B * self.n_blocks)
            if n_pages < self.n_blocks:
                raise ValueError(
                    f"cache_pages={n_pages} cannot back even one full-length"
                    f" request (ceil(max_len/page_size) = {self.n_blocks} "
                    f"pages); a preempted request could never resume")
            self.pool = PagePool(n_pages, ps)
            if obs.enabled:
                self.pool.bind_metrics(obs.metrics)
            if sc.prefix_cache:
                self.prefix = PrefixCache(
                    self.pool,
                    metrics=obs.metrics if obs.enabled else None)
            self.caches = T.init_paged_caches(cfg, B, n_pages, ps,
                                              jnp.dtype(sc.cache_dtype),
                                              tpctx=self.tp,
                                              kv_dtype=sc.kv_dtype)
            self.block_tables = np.zeros((B, self.n_blocks), np.int32)
            self.slot_tables: List[Optional[BlockTable]] = [None] * B
            self.slot_rid = np.full(B, -1, np.int64)
            self.wait: List[_Waiting] = []
            # rid → accumulated output stream. Entries persist past natural
            # retirement so the caller can read the finished stream; a
            # long-running server should request_out.pop(rid) once consumed
            # (cancel() and generate()'s reset drop theirs automatically).
            self.request_out: Dict[int, List[int]] = {}
            self._next_rid = 0
        else:
            self.caches = T.init_caches(cfg, B, sc.max_len,
                                        jnp.dtype(sc.cache_dtype),
                                        tpctx=self.tp)
        self.slot_pos = np.zeros(B, np.int32)
        self.slot_live = np.zeros(B, bool)
        self.slot_out: List[List[int]] = [[] for _ in range(B)]
        # The ORIGINAL prompt per slot: prefix-cache indexing (paged) and
        # the drafter's context (spec) both need it; dense engines fill it
        # too so speculation works on contiguous caches.
        self.slot_prompt: List[List[int]] = [[] for _ in range(B)]
        # Next sampled token per slot, already decoded but not yet reported:
        # seeded by submit() from the prefill logits, advanced by step().
        self.slot_next = np.zeros(B, np.int32)
        # Draining slots hold a final pending token but may not decode
        # further (their cache is full): step() reports it, then retires —
        # the freshly decoded last token is never silently dropped.
        self.slot_drain = np.zeros(B, bool)
        # Chunked prefill: a prefilling slot is live (it holds its pages
        # and its slot) but not yet decodable; step() advances one chunk
        # per iteration (scheduler.prefill_chunk tokens) until done.
        self.slot_prefilling = np.zeros(B, bool)
        self.slot_pf_tokens: List[Optional[List[int]]] = [None] * B
        self.slot_pf_restore: List[Optional[_Waiting]] = [None] * B
        self.slot_pf_key: List[Optional[jax.Array]] = [None] * B
        # Per-request scheduling metadata the scheduler sees via _view().
        self.slot_priority = np.zeros(B, np.int64)
        self.slot_deadline: List[Optional[float]] = [None] * B
        self.slot_arrival = np.zeros(B, np.int64)
        # Observability (stats()): a monotonic host tick (one per submit()
        # and step() call) orders arrivals; token counters split prefill
        # from decode work, and row counters count what the model computed
        # for it (B * Sb per prefill call, B per decode call, padding
        # included). engine_s is host time inside submit()/step()/cancel(),
        # engine_wait_s the part of it blocked on device results.
        self.tick = 0
        self.n_preemptions = 0
        self.prefill_tokens = 0
        self.decode_tokens = 0
        self.prefill_rows = 0
        self.decode_rows = 0
        self.engine_s = 0.0
        self.engine_wait_s = 0.0
        # Speculative-decoding counters (stats()): drafted tokens the
        # target's greedy choice confirmed vs rejected, and pool pages
        # returned by rejection rollback (BlockTable.truncate).
        self.spec_accepted = 0
        self.spec_rejected = 0
        self.spec_rollback_pages = 0

    # -- shared helpers -----------------------------------------------------
    def _sample(self, logits: jax.Array,
                key: Optional[jax.Array] = None) -> jax.Array:
        """The single sampling rule shared by generate(), submit() and
        step(): greedy argmax at temperature 0 (or when no PRNG key is
        supplied), softmax sampling at ServeConfig.temperature otherwise."""
        if self.sc.temperature > 0 and key is not None:
            return jax.random.categorical(
                key, logits.astype(jnp.float32) / self.sc.temperature,
                axis=-1)
        return jnp.argmax(logits, axis=-1)

    @contextlib.contextmanager
    def _timed(self, name: str, *, wait: bool = False, **args):
        """Span ``name`` (with ``args``) round the block, and its host
        seconds added to ``engine_s`` — or, with ``wait``, to
        ``engine_wait_s``: submit(), step() and cancel() run inside the
        former, every block on the device inside the latter."""
        t0 = time.perf_counter()
        try:
            with self.obs.trace.span(name, **args):
                yield
        finally:
            dt = time.perf_counter() - t0
            if wait:
                self.engine_wait_s += dt
            else:
                self.engine_s += dt

    def _wait(self, x: jax.Array, what: str) -> np.ndarray:
        """Bring device result ``x`` to the host inside an ``engine.wait``
        span (``what`` names the result)."""
        with self._timed("engine.wait", wait=True, what=what):
            return np.asarray(x)

    def _wait_caches(self):
        """Wait, inside an ``engine.wait`` span, for any call still writing
        the caches (a prefill chunk submit() dispatched), before a dispatch
        that makes a fresh copy of them. The caches are not donated, so a
        call in flight holds its input and output copies; where the device
        cannot hold one more, that dispatch would block in the runtime's
        allocator until the call ends, a wait ``engine_wait_s`` would not
        see. The dispatch then goes out when it would have anyway."""
        with self._timed("engine.wait", wait=True, what="caches"):
            jax.block_until_ready(self.caches)

    def _reset_slot_caches(self, slot: int):
        """Zero a slot's valid lengths so a recycled slot starts from
        position 0 (stale K/V beyond len=0 — contiguous rows or recycled
        pool pages alike — is invisible to attention)."""
        def rec(node):
            if isinstance(node, dict):
                if "state" in node:
                    # SSD recurrent state carries no positions/len; submit
                    # only admits these with batch_slots == 1 (see below),
                    # where the whole state belongs to this slot.
                    return jax.tree_util.tree_map(jnp.zeros_like, node)
                out = {k: rec(v) for k, v in node.items()}
                if "len" in out:
                    out["len"] = out["len"].at[..., slot].set(0)
                return out
            if isinstance(node, (list, tuple)):
                return type(node)(rec(v) for v in node)
            return node
        self.caches = rec(self.caches)

    def _set_slot_len(self, slot: int, n: int):
        """Preload a slot's valid length: prefix-cache admission reuses
        ``n`` tokens already resident in shared/forked pages, and the
        cache-len update is *additive* (len + tokens written), so the
        partial prefill must start from the reused count — otherwise the
        kernels' kv_valid_len would undercount and mask live keys."""
        def rec(node):
            if isinstance(node, dict):
                out = {k: rec(v) for k, v in node.items()}
                if "len" in out:
                    out["len"] = out["len"].at[..., slot].set(n)
                return out
            if isinstance(node, (list, tuple)):
                return type(node)(rec(v) for v in node)
            return node
        self.caches = rec(self.caches)

    def _set_slot_lens(self, updates: Dict[int, int]):
        """Batched :meth:`_set_slot_len`: one cache-tree pass setting
        several slots' valid lengths at once. The speculative-decoding
        rollback path uses this — a verify pass wrote 1 + k tokens per
        slot (the len update is additive inside the jitted forward), and
        every slot with rejected drafts must shrink back to its accepted
        count before the next forward reads kv_valid_len."""
        if not updates:
            return
        idx = np.fromiter(updates.keys(), np.int32, count=len(updates))
        val = jnp.asarray(
            np.fromiter(updates.values(), np.int32, count=len(updates)))

        def rec(node):
            if isinstance(node, dict):
                out = {k: rec(v) for k, v in node.items()}
                if "len" in out:
                    out["len"] = out["len"].at[..., idx].set(val)
                return out
            if isinstance(node, (list, tuple)):
                return type(node)(rec(v) for v in node)
            return node
        self.caches = rec(self.caches)

    def _copy_page(self, src: int, dst: int):
        """Copy-on-write device copy: duplicate page ``src``'s K/V rows
        into private page ``dst`` across every layer's pools. The page
        axis is -4 in both stacked scan leaves (n_scan, P, ps, Hkv, dh)
        and dense leaves (P, ps, Hkv, dh), and it is never sharded under
        TP (heads are), so the same indexed copy works mesh-resident —
        every shard duplicates its own head slice, keeping per-shard pools
        in lockstep."""
        self._wait_caches()

        def rec(node):
            if isinstance(node, dict):
                out = {}
                for k, v in node.items():
                    if k in ("kp", "vp"):
                        out[k] = v.at[..., dst, :, :, :].set(
                            v[..., src, :, :, :])
                    elif k in ("k_scale", "v_scale"):
                        # int8 pools: the (…, P, Hkv) frozen scale travels
                        # with the payload it quantized — a COW fork stays
                        # bitwise identical to the donor page.
                        out[k] = v.at[..., dst, :].set(v[..., src, :])
                    else:
                        out[k] = rec(v)
                return out
            if isinstance(node, (list, tuple)):
                return type(node)(rec(v) for v in node)
            return node
        self.caches = rec(self.caches)

    def _dev(self, x) -> jax.Array:
        """Host → device: replicated over the TP mesh when one is active
        (mixed single-device/committed inputs alongside mesh-sharded params
        would otherwise be placement-ambiguous), plain asarray else."""
        if self.tp is None:
            return jnp.asarray(x)
        return TP.replicate(x, self.tp)

    def kv_shards(self) -> int:
        """Model shards each KV cache/page-pool tensor splits across (1
        when unsharded). Pool admission stays in logical pages — every
        shard mirrors the same allocation over its head slice — so this is
        the divisor turning pool bytes into *per-shard* resident bytes
        (benchmarks/serving_sweep.py --tp, docs/serving.md)."""
        if self.tp is None or self.cfg.is_mla:
            # the MLA latent cache (ckv/krope) has no head dim to split —
            # it replicates on every shard even when attention heads shard
            return 1
        _, shard_kv = TP.head_sharding(self.tp, self.cfg.n_heads,
                                       self.cfg.n_kv_heads)
        return self.tp.model_size if shard_kv else 1

    def kv_page_bytes(self) -> int:
        """Logical device bytes per pool page, summed over layers and K/V —
        including int8 pools' fp32 scale side-tensors, so this is the unit
        the capacity sweep's pool-byte budget is denominated in
        (benchmarks/serving_sweep.py). Divide by :meth:`kv_shards` for
        per-shard bytes under TP."""
        total = 0

        def rec(node):
            nonlocal total
            if isinstance(node, dict):
                for k, v in node.items():
                    if k in ("kp", "vp", "k_scale", "v_scale"):
                        total += v.size * v.dtype.itemsize
                    else:
                        rec(v)
            elif isinstance(node, (list, tuple)):
                for v in node:
                    rec(v)

        rec(self.caches)
        return total // self.pool.n_pages

    def _bt_device(self) -> jnp.ndarray:
        return self._dev(self.block_tables)

    def _handle(self, slot: int) -> int:
        """What submit()/step() key results by: request id in paged mode
        (requests migrate across slots under preemption), slot id else."""
        return int(self.slot_rid[slot]) if self.paged else slot

    def _view(self, slot: int) -> RequestView:
        """The read-only snapshot the scheduler judges a live slot by.
        ``lookahead`` tells the policy how many *speculated* positions
        this request may additionally claim pages for next step — its
        page appetite under ServeConfig.spec is 1 + lookahead, not 1."""
        spec_ahead = (int(self.spec.k)
                      if self.spec is not None
                      and not self.slot_prefilling[slot]
                      and not self.slot_drain[slot] else 0)
        return RequestView(
            rid=self._handle(slot),
            priority=int(self.slot_priority[slot]),
            deadline=self.slot_deadline[slot],
            arrival=int(self.slot_arrival[slot]),
            n_tokens=int(self.slot_pos[slot]),
            prefilling=bool(self.slot_prefilling[slot]),
            lookahead=spec_ahead)

    def _slot_of_rid(self, rid: int) -> int:
        """The live slot holding request ``rid``; raises a descriptive
        RuntimeError when no live slot does. Victim resolution goes
        through here — a Scheduler.victim subclass returning a rid that
        is not live used to surface as a bare StopIteration from
        ``next()``, which reads as an internal iterator bug instead of a
        policy-contract violation."""
        for s in range(self.sc.batch_slots):
            if self.slot_live[s] and self._handle(s) == rid:
                return s
        live = sorted(self._handle(s) for s in range(self.sc.batch_slots)
                      if self.slot_live[s])
        raise RuntimeError(
            f"scheduler victim() returned rid {rid}, which is not a live "
            f"request (live rids: {live}); victim() must return the rid "
            f"of one of the RequestViews it was passed "
            f"(serving/scheduler.py)")

    # -- single-prompt helpers (used by tests/examples) ---------------------
    def generate(self, prompts: np.ndarray, n_tokens: int,
                 key: Optional[jax.Array] = None) -> np.ndarray:
        """prompts: (B, S) int32 — B must equal batch_slots. Returns
        (B, n_tokens) generated ids. In paged mode the pool is reset (all
        in-flight submit() requests dropped, the prefix cache cleared) and
        every row gets pages for its full S + n_tokens horizon up front."""
        B, S = prompts.shape
        if B != self.sc.batch_slots:
            raise ValueError(
                f"generate() got prompts shaped {tuple(prompts.shape)} "
                f"(batch {B}), but this engine was built with "
                f"ServeConfig.batch_slots={self.sc.batch_slots}; the "
                f"batched path needs one prompt per slot")
        bt = None
        if self.paged:
            if S + n_tokens > self.sc.max_len:
                raise ValueError(
                    f"generate() horizon S+n_tokens = {S + n_tokens} "
                    f"exceeds max_len={self.sc.max_len}")
            self._reset_paged_state()
            need = self.pool.pages_needed(S + n_tokens)
            if not self.pool.can_alloc(need * B):
                raise ValueError(
                    f"batched generate needs {need * B} pages "
                    f"({need}/row), pool holds {self.pool.n_pages}; raise "
                    f"cache_pages or use submit()/step() admission")
            for s in range(B):
                tbl = BlockTable(self.pool)
                tbl.ensure(S + n_tokens)
                self.slot_tables[s] = tbl
                tbl.as_row(self.n_blocks, out=self.block_tables[s])
            bt = self._bt_device()
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        batch = {"tokens": self._dev(prompts),
                 "positions": self._dev(positions)}
        if bt is not None:
            batch["block_tables"] = bt
        logits, self.caches = self.prefill(self.params, batch, self.caches)
        out = []
        key, sub = (jax.random.split(key) if key is not None
                    else (None, None))
        tok = self._sample(logits, sub)[:, None].astype(jnp.int32)
        for i in range(n_tokens):
            out.append(np.asarray(tok)[:, 0])
            pos = self._dev(jnp.full((B, 1), S + i, jnp.int32))
            logits, self.caches = self.decode(self.params, tok, pos,
                                              self.caches, bt)
            key, sub = (jax.random.split(key) if key is not None
                        else (None, None))
            tok = self._sample(logits, sub)[:, None].astype(jnp.int32)
        if self.paged:
            # the generated tokens are complete and no slot is live — the
            # horizon pages are dead; returning them keeps a later
            # submit() from inheriting (and silently dropping) ownership
            self._reset_paged_state()
        return np.stack(out, axis=1)

    def _reset_paged_state(self):
        """Drop every in-flight request and return all pages to the pool
        (batched generate() owns the whole engine). The prefix cache is
        cleared too — its retained pages would otherwise pin pool capacity
        a full-batch generate() is entitled to."""
        if self.prefix is not None:
            self.prefix.clear()
        for s in range(self.sc.batch_slots):
            if self.slot_tables[s] is not None:
                self.slot_tables[s].free()
                self.slot_tables[s] = None
            if self.slot_live[s]:       # dropped mid-flight: stream is dead
                self.request_out.pop(int(self.slot_rid[s]), None)
        for w in self.wait:
            self.request_out.pop(w.rid, None)
        # Zero every row's valid length unconditionally: generate() writes
        # caches without advancing slot_pos, so per-slot reset heuristics
        # would let `len` accumulate across generate() calls (inflating
        # kv_valid_len past the block-table-backed range — garbage keys
        # under non-causal attention, dead block-skip under causal).
        def rec(node):
            if isinstance(node, dict):
                return {k: (jnp.zeros_like(v) if k == "len" else rec(v))
                        for k, v in node.items()}
            if isinstance(node, (list, tuple)):
                return type(node)(rec(v) for v in node)
            return node
        self.caches = rec(self.caches)
        # in-flight lifecycle records die with their requests (their open
        # async spans are auto-closed at export time)
        self.request_traces.clear()
        self.block_tables[:] = 0
        self.slot_rid[:] = -1
        self.slot_live[:] = False
        self.slot_drain[:] = False
        self.slot_pos[:] = 0
        self.slot_prefilling[:] = False
        self.slot_pf_tokens = [None] * self.sc.batch_slots
        self.slot_pf_restore = [None] * self.sc.batch_slots
        self.slot_pf_key = [None] * self.sc.batch_slots
        self.wait.clear()

    # -- continuous batching -------------------------------------------------
    def submit(self, prompt: List[int],
               key: Optional[jax.Array] = None, *,
               priority: int = 0,
               deadline: Optional[float] = None) -> Optional[int]:
        """Admit a request; returns its handle (paged: request id,
        contiguous: slot id) or None when it cannot be admitted now.

        Masked single-slot prefill: the prompt runs as prefill calls in
        which every *other* batch row carries position -1 — the attention
        cache update skips those rows entirely (no K/V write, no
        valid-length bump), so concurrent slots' caches are untouched.
        (The old per-token full-batch decode wrote zero-token K/V into every
        other live slot's cache and inflated their lengths — the
        interleaved-submit corruption regression in tests/test_serving.py.)

        **Bucketed prefill**: each prefill call is right-padded to the next
        power-of-two length with position −1 columns (dropped from the
        cache write, zero rows in attention), so at most log2(max_len)
        prefill programs ever compile instead of one per distinct prompt
        length; the logits seeding the first token are read from the last
        *real* column, leaving the token stream bit-identical to an
        unpadded prefill (the regression test in tests/test_serving.py).

        **Chunked prefill** (scheduler.prefill_chunk = N): submit runs only
        the first N prompt tokens; step() advances one chunk per iteration,
        interleaved with decode, bounding decode-latency jitter under long
        prompts. The default (None) prefills the whole prompt here — the
        PR 4/5 behavior.

        The prefill's last-position logits seed the slot's pending greedy
        token, so the first decode step is conditioned on the real prompt,
        not a pseudo-BOS; step() reports that token first — no token of the
        stream is lost. Recycled slots restart from position 0 with their
        valid lengths zeroed.

        Paged admission is page-bound: a free slot AND enough free pages to
        cover the prompt (decode growth allocates on demand; the padding
        columns cost nothing — pages back real tokens only). With the
        prefix cache, cached full prompt pages are *borrowed* instead of
        allocated (the first divergent page is forked copy-on-write), so
        only the uncached tail needs free pages — and prefills. ``priority``
        (0 = most urgent) and ``deadline`` feed the scheduler: an incoming
        request may preempt a strictly less urgent live one
        (scheduler.should_preempt) instead of returning None.
        """
        if self.cfg.family in ("ssm", "hybrid") and self.sc.batch_slots > 1:
            raise NotImplementedError(
                "slot-based submit() requires position-masked cache updates; "
                "SSD/conv recurrent states carry no positions, so a masked "
                "single-slot prefill cannot leave other slots' SSM state "
                "untouched. Use generate(), or batch_slots=1 where no other "
                "slot exists.")
        if not 0 < len(prompt) < self.sc.max_len:
            raise ValueError(
                f"prompt length {len(prompt)} out of range for "
                f"max_len={self.sc.max_len} (need 1 <= len < max_len)")
        with self._timed("engine.admit", prompt_len=len(prompt)):
            prompt = [int(t) for t in prompt]
            self.tick += 1
            arrival = self.tick
            if not self.paged:
                free = np.where(~self.slot_live)[0]
                if free.size == 0:
                    return None
                slot = int(free[0])
                self.slot_priority[slot] = priority
                self.slot_deadline[slot] = deadline
                self.slot_arrival[slot] = arrival
                self.slot_prompt[slot] = prompt
                self._begin_admit(slot, prompt, key=key)
                return slot
            incoming = RequestView(rid=self._next_rid, priority=priority,
                                   deadline=deadline, arrival=arrival,
                                   n_tokens=len(prompt))
            while True:
                free = np.where(~self.slot_live)[0]
                if free.size and self._paged_admit(
                        int(free[0]), self._next_rid, prompt, prompt,
                        key=key, priority=priority, deadline=deadline,
                        arrival=arrival):
                    rid = self._next_rid
                    self._next_rid += 1
                    return rid
                # no slot, or not enough pages even after cold-cache eviction:
                # ask the policy whether this request may displace a live one
                live = [s for s in range(self.sc.batch_slots)
                        if self.slot_live[s]]
                if not live:
                    return None
                vrid = self.scheduler.victim([self._view(s) for s in live])
                vslot = self._slot_of_rid(vrid)
                if not self.scheduler.should_preempt(incoming,
                                                     self._view(vslot)):
                    return None          # page/slot-bound, not worth churning
                self._preempt(vslot)

    def _begin_admit(self, slot: int, tokens: List[int], *,
                     start: int = 0,
                     restore: Optional[_Waiting] = None,
                     key: Optional[jax.Array] = None):
        """Stage ``tokens`` into ``slot`` and run the first prefill chunk
        (the whole remainder unless the scheduler chunks). ``start`` > 0
        marks a prefix-cache hit: positions [0, start) are already resident
        in shared/forked pages, so the slot's valid length is preloaded and
        prefill begins mid-prompt. With ``restore`` (resume after
        preemption) the pending token and output stream are carried over
        instead of re-sampled, so the resumed stream is identical to an
        uninterrupted one under any sampling."""
        if self.slot_pos[slot]:        # recycled slot: restart from pos 0
            self._reset_slot_caches(slot)
            self.slot_pos[slot] = 0
        if start:
            self._set_slot_len(slot, start)
            self.slot_pos[slot] = start
        self.slot_live[slot] = True
        self.slot_drain[slot] = False
        self.slot_prefilling[slot] = True
        self.slot_pf_tokens[slot] = tokens
        self.slot_pf_restore[slot] = restore
        self.slot_pf_key[slot] = key
        self.slot_out[slot] = restore.out if restore is not None else []
        obs = self.obs
        if obs.enabled:
            h = self._handle(slot)
            now = time.perf_counter()
            if restore is None:
                rt = RequestTrace(
                    rid=h, prompt_len=len(tokens),
                    priority=int(self.slot_priority[slot]),
                    deadline=self.slot_deadline[slot], submit_s=now,
                    prefix_hit_tokens=start)
                self.request_traces[h] = rt
                self._m_admissions.inc()
                obs.trace.async_begin(h, {"prompt_len": len(tokens),
                                          "priority": rt.priority})
                if start:
                    obs.trace.async_instant(h, "prefix-hit",
                                            {"tokens": start})
            else:
                self._m_resumes.inc()
                obs.trace.async_instant(h, "resume",
                                        {"restart_tokens": len(tokens)})
                rt = self.request_traces.get(h)
                if rt is not None and rt.preempted_at_s is not None:
                    rt.wait_s += now - rt.preempted_at_s
                    rt.preempted_at_s = None
            self._m_live.set(int(self.slot_live.sum()))
        self._prefill_slot_chunk(slot)

    def _prefill_slot_chunk(self, slot: int) -> bool:
        """Run one masked, bucketed prefill chunk for ``slot``; returns
        True when the prompt is fully prefilled and the slot became
        decodable (pending token seeded, prompt pages indexed in the
        prefix cache)."""
        tokens = self.slot_pf_tokens[slot]
        L = len(tokens)
        p0 = int(self.slot_pos[slot])
        obs = self.obs
        budget = self.scheduler.prefill_chunk or (L - p0)
        n = min(budget, L - p0)
        B = self.sc.batch_slots
        # Bucket padding relies on the position −1 masking contract, which
        # SSD/conv recurrent state is outside of (it carries no positions):
        # pad columns would enter the recurrence as real tokens. Those
        # families (admitted only with batch_slots == 1) prefill unpadded.
        if self.cfg.family in ("ssm", "hybrid"):
            Sb = n
        else:
            Sb = min(_next_pow2(n), max(self.sc.max_len, n))
        h = self._handle(slot)
        # host assembly + dispatch
        with obs.trace.span("engine.prefill", rid=h, start=p0, tokens=n,
                            rows=B * Sb):
            tok = np.zeros((B, Sb), np.int32)
            tok[slot, :n] = tokens[p0:p0 + n]
            pos = np.full((B, Sb), -1, np.int32)
            pos[slot, :n] = np.arange(p0, p0 + n)
            batch = {"tokens": self._dev(tok), "positions": self._dev(pos),
                     "last_cols": self._dev(jnp.full((B,), n - 1,
                                                     jnp.int32))}
            if self.paged:
                batch["block_tables"] = self._bt_device()
            self._wait_caches()
            logits, self.caches = self.prefill(self.params, batch,
                                               self.caches)
        self.prefill_tokens += n
        self.prefill_rows += B * Sb
        self.slot_pos[slot] = p0 + n
        if obs.enabled:
            self._m_prefill_tokens.inc(n)
            rt = self.request_traces.get(h)
            if rt is not None:
                rt.prefill_chunks.append({"start_pos": p0, "tokens": n})
        if p0 + n < L:
            return False               # more chunks on later steps
        self.slot_prefilling[slot] = False
        self.slot_drain[slot] = L >= self.sc.max_len
        restore = self.slot_pf_restore[slot]
        if restore is not None and restore.next_tok is not None:
            self.slot_next[slot] = restore.next_tok
        else:
            # fresh admission — or a resume preempted before its first
            # sample existed: the stored key re-seeds it identically
            self.slot_next[slot] = int(self._wait(self._sample(
                logits[slot][None], self.slot_pf_key[slot]),
                "first_token")[0])
        if self.prefix is not None:
            # index the ORIGINAL prompt's full pages (never the generated
            # tail: decode writes positions >= len(prompt), so these pages
            # are write-free from here on — safe to share)
            prompt = self.slot_prompt[slot]
            if len(prompt) >= self.pool.page_size:
                n_full = len(prompt) // self.pool.page_size
                self.prefix.insert(prompt,
                                   self.slot_tables[slot].pages[:n_full])
        self.slot_pf_tokens[slot] = None
        self.slot_pf_restore[slot] = None
        self.slot_pf_key[slot] = None
        return True

    # -- paged scheduling ---------------------------------------------------
    def _ensure_free(self, n: int) -> bool:
        """True once the pool can cover ``n`` fresh pages, evicting cold
        prefix-cache entries on demand to get there."""
        if self.pool.can_alloc(n):
            return True
        if self.prefix is not None:
            short = n - self.pool.free_pages
            with self.obs.trace.span("engine.evict", requested=short,
                                     why="on-demand"):
                self.prefix.evict(short)
        return self.pool.can_alloc(n)

    def _paged_admit(self, slot: int, rid: int, prompt: List[int],
                     tokens: List[int], *,
                     restore: Optional[_Waiting] = None,
                     key: Optional[jax.Array] = None,
                     priority: int = 0, deadline: Optional[float] = None,
                     arrival: int = 0) -> bool:
        """Admit ``tokens`` into ``slot``: prefix lookup, page budget
        (evicting cold cache entries when short), COW fork of the first
        divergent page, block-table assembly, then masked prefill of the
        uncached tail. Returns False — with no side effects beyond the
        lookup's released holds — when pages cannot cover it."""
        hit = self.prefix.lookup(tokens) if self.prefix is not None else None
        n_covered = len(hit.pages) if hit is not None else 0
        need = self.pool.pages_needed(len(tokens)) - n_covered
        if not self._ensure_free(need):
            if hit is not None:
                hit.release(self.pool)
            return False
        assert self.slot_tables[slot] is None, \
            f"free slot {slot} still owns a block table (page leak)"
        start, pages = 0, []
        if hit is not None:
            self.prefix.record(hit, len(tokens))
            pages = hit.pages          # lookup's holds become the table's
            hit.pages = []
            start = hit.n_tokens
            if hit.cow_page is not None:
                # fork: private copy of the partially-matching page; its
                # leading cow_tokens rows are valid, the rest is overwritten
                # by the prefill (or masked by the valid length)
                dst = self.pool.fork(hit.cow_page)
                self._copy_page(hit.cow_page, dst)
                self.pool.release([hit.cow_page])   # drop lookup's hold
                hit.cow_page = None
                pages.append(dst)
                start += hit.cow_tokens
                self.prefix.note_cow_fork()
        tbl = BlockTable(self.pool, pages=pages)
        tbl.ensure(len(tokens))
        self.slot_tables[slot] = tbl
        tbl.as_row(self.n_blocks, out=self.block_tables[slot])
        self.slot_rid[slot] = rid
        self.slot_prompt[slot] = prompt
        self.slot_priority[slot] = priority
        self.slot_deadline[slot] = deadline
        self.slot_arrival[slot] = arrival
        self._begin_admit(slot, tokens, start=start, restore=restore,
                          key=key)
        if restore is None:
            self.request_out[rid] = self.slot_out[slot]
        return True

    def _preempt(self, slot: int):
        """Spill ``slot``'s request to the wait queue: free its pages, park
        prompt/stream/pending-token host-side. Its cache pages are
        recycled; resume re-prefills prompt+out — through the prefix cache
        when enabled, so a preempted request's shared prefix re-admits
        without re-prefilling (docs/serving.md)."""
        obs = self.obs
        h = self._handle(slot)       # before slot_rid resets below
        with obs.trace.span("engine.preempt", rid=h):
            if self.slot_prefilling[slot]:
                # mid-chunked-prefill: no pending token was sampled yet; park
                # the sampling key (and any carried token from an earlier
                # preemption) so resume reproduces the stream exactly
                restore = self.slot_pf_restore[slot]
                next_tok = None if restore is None else restore.next_tok
                key = self.slot_pf_key[slot]
            else:
                next_tok = int(self.slot_next[slot])
                key = None
            self.wait.append(_Waiting(
                rid=int(self.slot_rid[slot]), prompt=self.slot_prompt[slot],
                out=self.slot_out[slot], next_tok=next_tok, key=key,
                priority=int(self.slot_priority[slot]),
                deadline=self.slot_deadline[slot],
                arrival=int(self.slot_arrival[slot])))
            self.n_preemptions += 1
            self.slot_tables[slot].free()
            self.slot_tables[slot] = None
            self.block_tables[slot] = 0
            self.slot_rid[slot] = -1
            self.slot_live[slot] = False
            self.slot_drain[slot] = False
            self.slot_prefilling[slot] = False
            self.slot_pf_tokens[slot] = None
            self.slot_pf_restore[slot] = None
            self.slot_pf_key[slot] = None
            # slot_pos stays nonzero → the next admission resets the
            # slot's lens
        if obs.enabled:
            self._m_preemptions.inc()
            self._m_live.set(int(self.slot_live.sum()))
            self._m_waiting.set(len(self.wait))
            obs.trace.async_instant(h, "preempt")
            rt = self.request_traces.get(h)
            if rt is not None:
                rt.n_preemptions += 1
                rt.preempted_at_s = time.perf_counter()

    def _try_resume(self):
        """Re-admit waiting requests into free slots in the scheduler's
        order (default: FIFO within priority). A waiter that doesn't fit
        is *skipped*, not a barrier — the old strict-FIFO resume bailed on
        the first non-fitting request, head-of-line-blocking a small later
        one a free slot and pages existed for."""
        if not self.wait:
            return
        views = [RequestView(rid=w.rid, priority=w.priority,
                             deadline=w.deadline, arrival=w.arrival,
                             n_tokens=len(w.prompt) + len(w.out))
                 for w in self.wait]
        admitted = []
        obs = self.obs
        for i in self.scheduler.resume_order(views):
            free = np.where(~self.slot_live)[0]
            if free.size == 0:
                break
            w = self.wait[i]
            with obs.trace.span("engine.resume", rid=w.rid):
                if self._paged_admit(int(free[0]), w.rid, w.prompt,
                                     w.prompt + w.out, restore=w, key=w.key,
                                     priority=w.priority,
                                     deadline=w.deadline,
                                     arrival=w.arrival):
                    admitted.append(i)
        for i in sorted(admitted, reverse=True):
            self.wait.pop(i)
        if obs.enabled and admitted:
            self._m_waiting.set(len(self.wait))

    def _grow_pages_for_decode(self, drafts: Optional[Dict[int, List[int]]]
                               = None):
        """Back every decodable slot's next position with a page, oldest
        request first; when the pool is dry — after cold prefix entries
        are evicted — preempt the scheduler's victim (possibly the
        requester itself) until it isn't.

        ``drafts`` (speculative decoding) adds each slot's drafted
        positions to its page budget: the verify pass writes 1 + k
        tokens, so all of them must be page-backed up front. Speculated
        growth is strictly opportunistic — it never preempts (churning a
        live request for tokens that may be rejected is pure loss);
        instead the slot's draft list is trimmed in place to the
        positions the pool can actually back, degrading toward plain
        one-token decode under pressure."""
        order = sorted(
            (s for s in range(self.sc.batch_slots)
             if self.slot_live[s] and not self.slot_drain[s]
             and not self.slot_prefilling[s]),
            key=lambda s: self.slot_rid[s])
        for s in order:
            if not self.slot_live[s]:
                continue               # preempted by an older slot's growth
            pos = int(self.slot_pos[s])
            if pos >= self.slot_tables[s].capacity():
                while not self._ensure_free(1):
                    vrid = self.scheduler.victim(
                        [self._view(t) for t in range(self.sc.batch_slots)
                         if self.slot_live[t]])
                    victim = self._slot_of_rid(vrid)
                    self._preempt(victim)
                    if victim == s:
                        break          # self-preempted: wait queue, no grow
                if not self.slot_live[s]:
                    continue
                self.slot_tables[s].ensure(pos + 1)
            tbl = self.slot_tables[s]
            if drafts and drafts.get(s):
                m = len(drafts[s])
                need = self.pool.pages_needed(pos + 1 + m) - tbl.n_pages
                if need > 0:
                    if not self._ensure_free(need):
                        # trim to what the pool backs right now (never
                        # preempt for speculation); capacity() already
                        # covers pos + 1, so fit >= 0
                        fit = (tbl.capacity() + self.pool.free_pages
                               * self.pool.page_size) - (pos + 1)
                        drafts[s] = drafts[s][:max(fit, 0)]
                        need = (self.pool.pages_needed(
                            pos + 1 + len(drafts[s])) - tbl.n_pages)
                    if need > 0:
                        tbl.ensure(pos + 1 + len(drafts[s]))
            tbl.as_row(self.n_blocks, out=self.block_tables[s])

    def _retire(self, slot: int, *, cancelled: bool = False):
        """Release ``slot``. ``cancelled`` marks a caller-initiated abort
        (cancel() of a live request): the trace's async span then closes
        with ``{"cancelled": true}`` — matching the wait-queue cancel
        branch — and the cancelled counter moves instead of the retired
        one, so traces and slo_report() can tell an abort from a natural
        completion."""
        obs = self.obs
        if obs.enabled:
            h = self._handle(slot)   # before slot_rid resets below
            if cancelled:
                self._m_cancelled.inc()
                obs.trace.async_end(
                    h, {"cancelled": True,
                        "n_tokens": len(self.slot_out[slot])})
            else:
                self._m_retired.inc()
                obs.trace.async_end(h,
                                    {"n_tokens": len(self.slot_out[slot])})
            rt = self.request_traces.get(h)
            if rt is not None and rt.retire_s is None:
                rt.retire_s = time.perf_counter()
        self.slot_live[slot] = False
        self.slot_drain[slot] = False
        self.slot_prefilling[slot] = False
        self.slot_pf_tokens[slot] = None
        self.slot_pf_restore[slot] = None
        self.slot_pf_key[slot] = None
        if self.paged:
            self.slot_tables[slot].free()
            self.slot_tables[slot] = None
            self.block_tables[slot] = 0
            self.slot_rid[slot] = -1
        if obs.enabled:
            self._m_live.set(int(self.slot_live.sum()))

    def cancel(self, handle: int) -> bool:
        """Abort a request by the handle submit() returned (request id in
        paged mode, slot id else), releasing its slot — and, when paged,
        its pages (or its wait-queue entry). Returns True if found."""
        with self._timed("engine.cancel", handle=int(handle)):
            if not self.paged:
                if (0 <= handle < self.sc.batch_slots
                        and self.slot_live[handle]):
                    self._retire(handle, cancelled=True)
                    return True
                return False
            for s in range(self.sc.batch_slots):
                if self.slot_live[s] and self.slot_rid[s] == handle:
                    self._retire(s, cancelled=True)
                    self.request_out.pop(handle, None)
                    return True
            for i, w in enumerate(self.wait):
                if w.rid == handle:
                    self.wait.pop(i)
                    self.request_out.pop(handle, None)
                    if self.obs.enabled:
                        self._m_cancelled.inc()
                        self._m_waiting.set(len(self.wait))
                        self.obs.trace.async_end(handle, {"cancelled": True})
                        rt = self.request_traces.get(handle)
                        if rt is not None and rt.retire_s is None:
                            rt.retire_s = time.perf_counter()
                    return True
            return False

    def step(self, key: Optional[jax.Array] = None) -> Dict[int, int]:
        """One decode iteration across all live slots; non-live, draining
        and still-prefilling slots are masked out (position -1 → no cache
        write, no length bump). Returns {handle: token} — handles are
        request ids in paged mode, slot ids else.

        Reports each slot's *pending* token (decoded last round, or by the
        submit prefill) and pipelines the decode of the one after — the
        same order generate() uses, so slot streams match the batched path
        token for token. Sampling honors ServeConfig.temperature when a
        PRNG ``key`` is supplied (the same _sample rule as generate()).

        Paged mode first restores the prefix-cache watermark (evicting
        cold entries until ServeConfig.prefix_watermark pages are free),
        then resumes waiting requests in the scheduler's order, advances
        at most one chunked prefill (most urgent first), backs each
        decodable slot's next position with a page — evicting cold cache
        entries, then preempting the scheduler's victim when the pool is
        dry — and only then decodes. Retirement returns pages to the pool.

        A slot whose cache fills (slot_pos reaches max_len — every cache
        index written) enters a one-round *drain*: its final pending token
        — freshly decoded last round — is still reported before the slot
        retires, so no token of the stream is ever dropped at retirement.

        With ``ServeConfig.spec`` the iteration is speculative
        (:meth:`_spec_step`) and the result is ``{handle: [tokens]}`` —
        a burst of accepted tokens per request — instead of one token
        each; concatenated bursts equal the non-speculative stream
        exactly (docs/serving.md#speculative-decoding).

        Spans: ``engine.step`` holds ``engine.schedule`` (before and after
        the prefill chunk), ``engine.prefill``, ``engine.decode``,
        ``engine.wait`` and ``engine.emit`` (docs/observability.md).
        """
        self.tick += 1
        with self._timed("engine.step", tick=self.tick):
            obs = self.obs
            with obs.trace.span("engine.schedule"):
                if self.paged:
                    self._evict_to_watermark()
                    self._try_resume()
                # one chunked-prefill advance per step: bounded prefill
                # work keeps decode latency jitter bounded (the whole point
                # of chunking); unchunked admissions never appear here —
                # submit() finishes them
                pf = [s for s in range(self.sc.batch_slots)
                      if self.slot_prefilling[s]]
                chunk = min(pf, key=lambda t: (self.slot_priority[t],
                                               self.slot_arrival[t], t),
                            default=None)
            if not self.slot_live.any():
                return {}
            if chunk is not None:
                self._prefill_slot_chunk(chunk)
            if self.spec is not None:
                return self._spec_step()
            if self.paged:
                with obs.trace.span("engine.schedule"):
                    self._grow_pages_for_decode()
            decodable = (self.slot_live & ~self.slot_drain
                         & ~self.slot_prefilling)
            nxt = None
            if decodable.any():
                n_dec = int(decodable.sum())
                with obs.trace.span("engine.decode", slots=n_dec):
                    tok = self._dev(np.asarray(self.slot_next)[:, None])
                    pos = self._dev(np.where(decodable, self.slot_pos,
                                             -1).astype(np.int32)[:, None])
                    bt = self._bt_device() if self.paged else None
                    logits, self.caches = self.decode(self.params, tok, pos,
                                                      self.caches, bt)
                    ids = self._sample(logits, key)
                nxt = self._wait(ids, "decode_ids")
                self.decode_tokens += n_dec
                self.decode_rows += self.sc.batch_slots
                if obs.enabled:
                    self._m_decode_tokens.inc(n_dec)
            with obs.trace.span("engine.emit"):
                return self._emit(nxt)

    def _evict_to_watermark(self):
        """Evict cold prefix-cache entries until
        ``ServeConfig.prefix_watermark`` pages are free (step()'s first
        scheduling act)."""
        if self.prefix is None or self.sc.prefix_watermark <= 0:
            return
        short = self.sc.prefix_watermark - self.pool.free_pages
        if short > 0:
            with self.obs.trace.span("engine.evict", requested=short,
                                     why="watermark"):
                self.prefix.evict(short)

    def _emit(self, nxt: Optional[np.ndarray]) -> Dict[int, int]:
        """Report each live slot's pending token, retire drained slots and
        advance the rest to the ids ``nxt`` just decoded (the tail of
        :meth:`step`)."""
        out = {}
        for s in range(self.sc.batch_slots):
            if not self.slot_live[s] or self.slot_prefilling[s]:
                continue
            t = int(self.slot_next[s])
            self.slot_out[s].append(t)
            h = self._handle(s)
            out[h] = t
            if self.obs.enabled:
                self._obs_token(s, h, t)
            if self.slot_drain[s]:      # final pending token flushed above
                self._retire(s)
                continue
            self.slot_next[s] = int(nxt[s])
            self.slot_pos[s] += 1
            if self.slot_pos[s] >= self.sc.max_len:
                self.slot_drain[s] = True   # flush slot_next next round
        return out

    def _spec_step(self) -> Dict[int, List[int]]:
        """One speculative iteration: draft → verify → accept → rollback.

        Per decodable slot the drafter proposes up to ``spec.k`` tokens
        (capped to the ``max_len`` horizon, then — paged — to the pages
        the pool can back without preempting anyone). ONE verify forward
        runs every slot's row ``[pending] + drafts`` at positions
        ``pos..pos+m`` (fixed Sq = 1 + k, position −1 padded: a single
        compiled shape regardless of per-slot draft counts); column ``i``
        of its argmax is the target's greedy choice after consuming the
        row's tokens ``[0..i]``. Acceptance keeps the longest prefix of
        drafts the argmax agrees with, the column after the last accepted
        draft becomes the new pending (the "bonus" token — exactly what
        non-speculative decode would have sampled there), and rejected
        suffixes roll back: valid lengths reset to the accepted count
        (the jitted forward's len update is additive and counted every
        non-masked row) and wholly-rejected tail pages return to the pool
        (:meth:`BlockTable.truncate`). Every reported token is therefore
        the target's argmax given exactly the tokens before it — greedy
        streams are token-identical to ``spec=None`` by construction.

        Draining slots flush their pending final token as a one-token
        burst and retire, mirroring the non-speculative drain round.
        """
        obs = self.obs
        k = int(self.spec.k)
        decodable = (self.slot_live & ~self.slot_drain
                     & ~self.slot_prefilling)
        drafts: Dict[int, List[int]] = {}
        if decodable.any():
            w0 = self.spec.wait_s
            with obs.trace.span("engine.draft",
                                slots=int(decodable.sum())):
                for s in np.nonzero(decodable)[0]:
                    s = int(s)
                    pos = int(self.slot_pos[s])
                    # verify writes positions pos..pos+m; the last writable
                    # cache index is max_len - 1, so m <= max_len - 1 - pos
                    cap = min(k, self.sc.max_len - 1 - pos)
                    d: List[int] = []
                    if cap >= 1:
                        ctx = (self.slot_prompt[s] + self.slot_out[s]
                               + [int(self.slot_next[s])])
                        d = [int(t) for t in self.spec.draft(ctx, cap)][:cap]
                    drafts[s] = d
            # a drafter with a model blocks on its own device results
            self.engine_wait_s += self.spec.wait_s - w0
        if self.paged:
            # may preempt for the base pos+1 page and TRIM drafts in
            # place when speculation alone would exhaust the pool
            with obs.trace.span("engine.schedule"):
                self._grow_pages_for_decode(drafts)
            decodable = (self.slot_live & ~self.slot_drain
                         & ~self.slot_prefilling)
        nxt = None
        if decodable.any():
            B = self.sc.batch_slots
            with obs.trace.span("engine.verify",
                                slots=int(decodable.sum())):
                tok = np.zeros((B, 1 + k), np.int32)
                pos2 = np.full((B, 1 + k), -1, np.int32)
                for s in np.nonzero(decodable)[0]:
                    s = int(s)
                    d = drafts.get(s, [])
                    m = len(d)
                    p = int(self.slot_pos[s])
                    tok[s, 0] = int(self.slot_next[s])
                    tok[s, 1:1 + m] = d
                    pos2[s, :1 + m] = np.arange(p, p + 1 + m)
                batch = {"tokens": self._dev(tok),
                         "positions": self._dev(pos2)}
                if self.paged:
                    batch["block_tables"] = self._bt_device()
                greedy, self.caches = self.verify(self.params, batch,
                                                  self.caches)
            nxt = self._wait(greedy, "verify_ids")
            self.decode_rows += B * (1 + k)
        with obs.trace.span("engine.emit"):
            return self._spec_emit(drafts, nxt)

    def _spec_emit(self, drafts: Dict[int, List[int]],
                   nxt: Optional[np.ndarray]) -> Dict[int, List[int]]:
        """Report each slot's accepted burst, retire drained slots and roll
        rejected drafts back (the tail of :meth:`_spec_step`)."""
        obs = self.obs
        out: Dict[int, List[int]] = {}
        len_resets: Dict[int, int] = {}
        for s in range(self.sc.batch_slots):
            if not self.slot_live[s] or self.slot_prefilling[s]:
                continue
            h = self._handle(s)
            if self.slot_drain[s]:      # flush the final pending token
                t = int(self.slot_next[s])
                self.slot_out[s].append(t)
                out[h] = [t]
                if obs.enabled:
                    self._obs_token(s, h, t)
                self._retire(s)
                continue
            p = int(self.slot_pos[s])
            d = drafts.get(s, [])
            m = len(d)
            g = nxt[s]
            j = 0
            while j < m and d[j] == int(g[j]):
                j += 1
            burst = [int(self.slot_next[s])] + d[:j]
            for t in burst:
                self.slot_out[s].append(t)
                if obs.enabled:
                    self._obs_token(s, h, t)
            out[h] = burst
            self.spec_accepted += j
            self.spec_rejected += m - j
            self.decode_tokens += 1 + j
            new_pos = p + 1 + j
            if j < m:
                # rejected suffix: the verify pass wrote (and len-counted)
                # positions new_pos..p+m — shrink the valid length back
                # and return wholly-rejected tail pages to the pool
                len_resets[s] = new_pos
                if self.paged:
                    dropped = self.slot_tables[s].truncate(new_pos)
                    if dropped:
                        self.spec_rollback_pages += len(dropped)
                        if obs.enabled:
                            self._m_spec_rollback.inc(len(dropped))
                        self.slot_tables[s].as_row(
                            self.n_blocks, out=self.block_tables[s])
            if obs.enabled:
                self._m_decode_tokens.inc(1 + j)
                self._m_spec_accepted.inc(j)
                self._m_spec_rejected.inc(m - j)
                if m:
                    self._h_spec_accept.observe(j / m)
            self.slot_next[s] = int(g[j])
            self.slot_pos[s] = new_pos
            if new_pos >= self.sc.max_len:
                self.slot_drain[s] = True   # flush slot_next next round
        self._set_slot_lens(len_resets)
        return out

    # -- observability -------------------------------------------------------
    def _obs_token(self, slot: int, h: int, tok: int):
        """Per-reported-token trace/metrics. Called only when observability
        is enabled — the disabled step() loop pays one branch per token and
        never enters here."""
        now = time.perf_counter()
        rt = self.request_traces.get(h)
        if rt is not None:
            if rt.first_token_s is None:
                rt.first_token_s = now
                self._h_ttft.observe(now - rt.submit_s)
                self.obs.trace.async_instant(h, "first-token")
            else:
                self._h_itl.observe(now - rt.token_s[-1])
            rt.tokens.append(tok)
            rt.token_s.append(now)
            if self.paged:
                tbl = self.slot_tables[slot]
                pages = len(tbl.pages) if tbl is not None else 0
                tl = rt.pages_timeline
                if not tl or tl[-1][1] != pages:
                    tl.append((self.tick, pages))

    def request_trace(self, handle: int, pop: bool = False
                      ) -> Optional[RequestTrace]:
        """The lifecycle record for ``handle`` (repro.obs.RequestTrace):
        queue/preemption waits, prefill chunks, TTFT, the exact reported
        token stream with per-token timestamps, and the pages-held
        timeline. None when observability is
        disabled or the handle is unknown. Records persist past
        retirement; ``pop=True`` removes the record after returning it (a
        long-running server's analogue of ``request_out.pop``)."""
        if pop:
            return self.request_traces.pop(handle, None)
        return self.request_traces.get(handle)

    def stats(self) -> Dict[str, object]:
        """One flat observability snapshot: scheduling churn, prefill vs
        decode token split and the rows the model computed for them, host
        seconds inside submit()/step()/cancel() and the part of them spent
        waiting on the device, pool pressure, and (when enabled) the prefix
        cache's hit/miss/eviction counters. All are always on, whatever
        ``ServeConfig.obs`` is. Printed by launch/serve.py and
        recorded per-row in benchmarks/serving_sweep.py JSONL — every
        value is coerced to a plain JSON type (json_scalars), so the dict
        round-trips through json.dumps unchanged (tests/test_obs.py pins
        the schema)."""
        d: Dict[str, object] = {
            "tick": self.tick,
            "live_requests": int(self.slot_live.sum()),
            "waiting_requests": len(self.wait) if self.paged else 0,
            "n_preemptions": self.n_preemptions,
            "prefill_tokens": self.prefill_tokens,
            "decode_tokens": self.decode_tokens,
            "prefill_rows": self.prefill_rows,
            "decode_rows": self.decode_rows,
            "engine_s": self.engine_s,
            "engine_wait_s": self.engine_wait_s,
        }
        if self.spec is not None:
            seen = self.spec_accepted + self.spec_rejected
            d["spec_accepted_tokens"] = self.spec_accepted
            d["spec_rejected_tokens"] = self.spec_rejected
            d["spec_rollback_pages"] = self.spec_rollback_pages
            d["spec_acceptance_rate"] = (
                self.spec_accepted / seen if seen else 0.0)
        if self.paged:
            d["pool_pages"] = self.pool.n_pages
            d["pool_free_pages"] = self.pool.free_pages
            d["pool_pages_in_use"] = self.pool.pages_in_use
            d["pool_high_water"] = self.pool.high_water
            page_bytes = self.kv_page_bytes()
            d["kv_dtype"] = self.sc.kv_dtype or str(self.sc.cache_dtype)
            d["kv_page_bytes"] = page_bytes
            d["kv_pool_bytes"] = page_bytes * self.pool.n_pages
            d["kv_bytes_in_use"] = page_bytes * self.pool.pages_in_use
            if self.prefix is not None:
                d.update(self.prefix.stats())
        return json_scalars(d)
