"""Model assembly: decoder LMs (dense/GQA/MLA/MoE/SSM/hybrid), BERT, ViT.

Layers are stacked along a leading axis and executed with jax.lax.scan —
compile time stays flat in depth (essential for the 512-device dry-run of
80-layer models) and remat policies apply per layer.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed import tp as TP
from repro.distributed.sharding import shard, stack_axes
from repro.models import layers as Lyr
from repro.models import ssm as SSM
from repro.models.config import ModelConfig
from repro.models.module import ax, dense_init, embed_init, fold, norm_init

# ---------------------------------------------------------------------------
# Per-layer block init/apply
# ---------------------------------------------------------------------------

def _init_block(key, cfg: ModelConfig, dtype, moe_layer: bool,
                kind: Optional[str] = None):
    kind = kind or ("ssd" if cfg.family in ("ssm", "hybrid") else "attn")
    p, a = {}, {}
    if kind == "ssd":
        p["mix_norm"], a["mix_norm"] = norm_init(cfg.d_model, dtype)
        p["ssd"], a["ssd"] = SSM.init_ssd(fold(key, 1), cfg, dtype)
        return p, a
    p["attn_norm"], a["attn_norm"] = norm_init(
        cfg.d_model, dtype, with_bias=cfg.norm == "layernorm")
    if cfg.is_mla:
        p["attn"], a["attn"] = Lyr.init_mla(fold(key, 1), cfg, dtype)
    else:
        p["attn"], a["attn"] = Lyr.init_attention(fold(key, 1), cfg, dtype)
    p["mlp_norm"], a["mlp_norm"] = norm_init(
        cfg.d_model, dtype, with_bias=cfg.norm == "layernorm")
    if moe_layer:
        p["moe"], a["moe"] = Lyr.init_moe(fold(key, 2), cfg, dtype)
    else:
        p["mlp"], a["mlp"] = Lyr.init_mlp(fold(key, 2), cfg, dtype)
    return p, a


def _apply_block(p, cfg: ModelConfig, x, *, positions, cache=None,
                 block_tables=None):
    """Returns (y, new_cache, aux_loss). ``block_tables`` (B, n_blocks)
    accompanies paged KV caches (docs/serving.md); None otherwise."""
    aux = jnp.zeros((), jnp.float32)
    if "ssd" in p:
        h, cache = SSM.ssd_block(p["ssd"], cfg,
                                 Lyr.rmsnorm(p["mix_norm"], x), cache=cache)
        return x + h, cache, aux
    h, cache = (Lyr.mla_attention if cfg.is_mla else Lyr.attention)(
        p["attn"], cfg, Lyr.apply_norm(cfg, p["attn_norm"], x),
        positions=positions, cache=cache, block_tables=block_tables)
    x = x + h
    h2 = Lyr.apply_norm(cfg, p["mlp_norm"], x)
    if "moe" in p:
        h2, aux = Lyr.moe(p["moe"], cfg, h2)
    else:
        h2 = Lyr.mlp(p["mlp"], cfg, h2)
    return x + h2, cache, aux


# ---------------------------------------------------------------------------
# Model init
# ---------------------------------------------------------------------------

def model_axes(cfg: ModelConfig) -> Dict:
    """The logical-axis tree of :func:`init_model`'s params, without
    materializing a single weight: the init is traced abstractly
    (jax.eval_shape — no allocation, no RNG work) and the axes tree, which
    is plain Python metadata, is captured on the side. Used by TP serving
    to place params when the caller didn't keep init_model's second
    return (serving/engine.py)."""
    box = {}

    def capture(key):
        _, box["axes"] = init_model(key, cfg)
        return 0.0

    jax.eval_shape(capture, jax.random.PRNGKey(0))
    return box["axes"]


def init_model(key, cfg: ModelConfig) -> Tuple[Dict, Dict]:
    dtype = cfg.param_dtype
    p, a = {}, {}
    p["embed"], a["embed"] = embed_init(fold(key, 0), cfg.vocab, cfg.d_model,
                                        dtype)
    if cfg.n_codebooks:  # musicgen: one embedding table per codebook
        cb = jax.vmap(lambda k: embed_init(k, cfg.vocab, cfg.d_model,
                                           dtype)[0])(
            jax.random.split(fold(key, 9), cfg.n_codebooks))
        p["embed_cb"] = cb
        a["embed_cb"] = ax(None, "vocab", "embed")

    n_scan = cfg.n_layers - cfg.first_dense_layers
    # deepseek-style leading dense layers (own, unstacked params)
    for i in range(cfg.first_dense_layers):
        dense_cfg = dataclasses.replace(cfg, n_experts=0)
        p[f"dense_layer{i}"], a[f"dense_layer{i}"] = _init_block(
            fold(key, 100 + i), dense_cfg, dtype, moe_layer=False)

    if cfg.attn_every:  # zamba-style hybrid: scan groups + shared attn block
        assert n_scan % cfg.attn_every == 0, (n_scan, cfg.attn_every)
        p["shared_attn"], a["shared_attn"] = _init_block(
            fold(key, 7), cfg, dtype, moe_layer=False, kind="attn")

    def one_layer(k):
        return _init_block(k, cfg, dtype, moe_layer=cfg.is_moe)[0]

    keys = jax.random.split(fold(key, 1), n_scan)
    p["layers"] = jax.vmap(one_layer)(keys)
    _, layer_axes = _init_block(fold(key, 1), cfg, dtype, moe_layer=cfg.is_moe)
    a["layers"] = stack_axes(layer_axes)

    p["final_norm"], a["final_norm"] = norm_init(
        cfg.d_model, dtype, with_bias=cfg.norm == "layernorm")
    head_vocab = cfg.vocab * max(cfg.n_codebooks, 1)
    p["head"], a["head"] = dense_init(fold(key, 2), cfg.d_model, head_vocab,
                                      dtype, ("embed", "vocab"), scale=0.02)
    return p, a


def init_sharded_model(key, cfg: ModelConfig,
                       tpctx: TP.TPContext) -> Tuple[Dict, Dict]:
    """:func:`init_model` with every weight created in its TP placement
    (:func:`repro.distributed.tp.param_shardings`): the jitted init writes
    each shard on its own device, so no device ever holds the whole model —
    the only way to build one that does not fit a single chip. The values
    equal :func:`init_model`'s (the PRNG is partitionable)."""
    axes = model_axes(cfg)

    def init(k):
        return init_model(k, cfg)[0]

    shardings = TP.param_shardings(axes, jax.eval_shape(init, key), tpctx)
    return jax.jit(init, out_shardings=shardings)(key), axes


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def embed_tokens(p, cfg: ModelConfig, batch: Dict[str, jax.Array]):
    if "embeds" in batch and batch["embeds"] is not None:
        x = batch["embeds"]                      # stubbed modality frontend
        if "tokens" in batch and batch["tokens"] is not None:
            t = jnp.take(p["embed"], batch["tokens"], axis=0)
            x = jnp.concatenate([x.astype(t.dtype), t], axis=1)  # vlm: img ⊕ text
        return x
    tokens = batch["tokens"]
    if cfg.n_codebooks:                          # (B,S,n_codebooks) token ids
        # p["embed_cb"]: (CB, vocab, D) — per-codebook tables, summed
        x = sum(jnp.take(p["embed_cb"][c], tokens[..., c], axis=0)
                for c in range(cfg.n_codebooks))
        return x
    return jnp.take(p["embed"], tokens, axis=0)


def _remat(fn, cfg: ModelConfig):
    """jax.checkpoint with the config's remat policy."""
    if cfg.remat_policy == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def _index_tree(tree, i: int):
    return jax.tree_util.tree_map(lambda t: t[i], tree)


def _stack_tree(trees):
    return jax.tree_util.tree_map(lambda *ts: jnp.stack(ts), *trees)


def _loop_layers(p, cfg: ModelConfig, x, positions, caches, remat: bool,
                 block_tables=None):
    """Unrolled (Python-loop) layer stack — numerically identical to
    _scan_layers; used by the dry-run for exact cost accounting (XLA's
    cost_analysis counts scan bodies once) and available for short models
    where unrolling compiles fine and pipelines marginally better."""
    n_scan = cfg.n_layers - cfg.first_dense_layers
    aux = jnp.zeros((), jnp.float32)

    def block(lp, x, lc):
        return _apply_block(lp, cfg, x, positions=positions, cache=lc,
                            block_tables=block_tables)

    block_fn = _remat(block, cfg) if remat else block

    if cfg.attn_every:
        g = cfg.attn_every
        ssm_caches, attn_caches = caches if caches is not None else (None,
                                                                     None)
        new_ssm, new_attn = [], []
        for gi in range(n_scan // g):
            grp_ssm = []
            for li in range(g):
                idx = gi * g + li
                lc = (_index_tree(_index_tree(ssm_caches, gi), li)
                      if caches is not None else None)
                x, c_new, aux_i = block_fn(_index_tree(p["layers"], idx), x,
                                           lc)
                aux += aux_i
                grp_ssm.append(c_new)
            sc = (_index_tree(attn_caches, gi)
                  if caches is not None else None)
            x, sc_new, _ = block_fn(p["shared_attn"], x, sc)
            if caches is not None:
                new_ssm.append(_stack_tree(grp_ssm))
                new_attn.append(sc_new)
        if caches is not None:
            return x, (_stack_tree(new_ssm), _stack_tree(new_attn)), aux
        return x, None, aux

    new_caches = []
    for i in range(n_scan):
        lc = _index_tree(caches, i) if caches is not None else None
        x, c_new, aux_i = block_fn(_index_tree(p["layers"], i), x, lc)
        aux += aux_i
        new_caches.append(c_new)
    out_caches = _stack_tree(new_caches) if caches is not None else None
    return x, out_caches, aux


def _scan_layers(p, cfg: ModelConfig, x, positions, caches, remat: bool,
                 block_tables=None):
    """Scan the stacked layer params (+ optional stacked caches) over x."""
    if not cfg.scan_layers:
        return _loop_layers(p, cfg, x, positions, caches, remat, block_tables)
    n_scan = cfg.n_layers - cfg.first_dense_layers
    zero = jnp.zeros((), jnp.float32)

    def body(carry, inp):
        x, aux = carry
        lp, lc = inp if caches is not None else (inp, None)
        y, new_c, aux_i = _apply_block(lp, cfg, x, positions=positions,
                                       cache=lc, block_tables=block_tables)
        return (y, aux + aux_i), new_c

    body_fn = _remat(body, cfg) if remat else body

    if cfg.attn_every:
        g = cfg.attn_every
        grouped = jax.tree_util.tree_map(
            lambda t: t.reshape((n_scan // g, g) + t.shape[1:]), p["layers"])

        def group_body(carry, inp):
            (x, aux) = carry
            if caches is not None:
                gp, gc, sc = inp   # group params, group ssm caches, attn cache
                (x, aux), gc_new = jax.lax.scan(body_fn, (x, aux), (gp, gc))
            else:
                gp, gc_new, sc = inp, None, None
                (x, aux), _ = jax.lax.scan(body_fn, (x, aux), gp)
            y, sc_new, _ = _apply_block(p["shared_attn"], cfg, x,
                                        positions=positions, cache=sc)
            out = (gc_new, sc_new) if caches is not None else None
            return (y, aux), out

        if caches is not None:
            ssm_caches, attn_caches = caches
            (x, aux), (ssm_new, attn_new) = jax.lax.scan(
                group_body, (x, zero), (grouped, ssm_caches, attn_caches))
            return x, (ssm_new, attn_new), aux
        (x, aux), _ = jax.lax.scan(group_body, (x, zero), grouped)
        return x, None, aux

    xs = (p["layers"], caches) if caches is not None else p["layers"]
    (x, aux), new_caches = jax.lax.scan(body_fn, (x, zero), xs)
    return x, new_caches, aux


def forward(params, cfg: ModelConfig, batch, *, caches=None,
            remat: Optional[bool] = None):
    """Returns (logits, new_caches, aux). batch: tokens (B,S) [+ embeds,
    positions, block_tables]. caches=None → full self-attention
    (training/scoring). ``block_tables`` (B, n_blocks) int32 accompanies
    paged KV caches (init_paged_caches): every layer's attention reads and
    writes its page pool through the same table (docs/serving.md)."""
    remat = cfg.remat if remat is None else remat
    if cfg.remat_policy == "none":
        remat = False
    x = embed_tokens(params, cfg, batch)
    B, S = x.shape[:2]
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    block_tables = batch.get("block_tables")
    x = shard(x, "act_batch", "act_seq", "act_embed")
    aux_total = jnp.zeros((), jnp.float32)

    dense_caches = None
    if caches is not None and cfg.first_dense_layers:
        caches, dense_caches = caches["scan"], caches["dense"]
    elif caches is not None and not cfg.first_dense_layers:
        caches = caches["scan"]

    new_dense = []
    for i in range(cfg.first_dense_layers):
        dense_cfg = dataclasses.replace(cfg, n_experts=0)
        c_i = dense_caches[i] if dense_caches is not None else None
        x, c_i, aux_i = _apply_block(params[f"dense_layer{i}"], dense_cfg, x,
                                     positions=positions, cache=c_i,
                                     block_tables=block_tables)
        new_dense.append(c_i)
        aux_total += aux_i

    x, new_scan, aux = _scan_layers(params, cfg, x, positions, caches, remat,
                                    block_tables)
    aux_total += aux
    x = Lyr.apply_norm(cfg, params["final_norm"], x)
    # vocab-column-parallel under TP (each shard computes its logit slice;
    # sampling consumes the global array) — api.linear without a context
    logits = TP.linear(x, params["head"], axes=("embed", "vocab"))
    logits = shard(logits, "act_batch", "act_seq", "act_vocab")
    if cfg.n_codebooks:
        logits = logits.reshape(B, S, cfg.n_codebooks, cfg.vocab)
    new_caches = {"scan": new_scan}
    if cfg.first_dense_layers:
        new_caches["dense"] = new_dense
    return logits, new_caches, aux_total


def _place_caches(cfg: ModelConfig, caches, tpctx):
    """Shard fresh caches onto a TP mesh: K/V leaves split on the KV-head
    dim exactly when tp.attention will shard them (tp.head_sharding), the
    rest replicated. No-op without a context."""
    if tpctx is None:
        return caches
    _, shard_kv = TP.head_sharding(tpctx, cfg.n_heads, cfg.n_kv_heads)
    return TP.shard_caches(caches, tpctx, shard_kv=shard_kv)


def init_caches(cfg: ModelConfig, batch: int, max_len: int, dtype,
                tpctx=None):
    """Stacked per-layer decode caches matching the scan structure.
    ``tpctx`` (a :class:`repro.distributed.tp.TPContext`) places the caches
    mesh-sharded for TP serving."""
    n_scan = cfg.n_layers - cfg.first_dense_layers

    def one_cache():
        if cfg.family == "ssm":
            return SSM.init_ssd_cache(cfg, batch, dtype)
        if cfg.is_mla:
            return Lyr.init_mla_cache(cfg, batch, max_len, dtype)
        return Lyr.init_attention_cache(cfg, batch, max_len, dtype)

    def stack(n, tree):
        return jax.tree_util.tree_map(
            lambda t: jnp.broadcast_to(t[None], (n,) + t.shape).copy(), tree)

    if cfg.attn_every:
        g = cfg.attn_every
        ssm = jax.tree_util.tree_map(
            lambda t: jnp.broadcast_to(t[None, None],
                                       (n_scan // g, g) + t.shape).copy(),
            SSM.init_ssd_cache(cfg, batch, dtype))
        attn = stack(n_scan // g,
                     Lyr.init_attention_cache(cfg, batch, max_len, dtype))
        caches = {"scan": (ssm, attn)}
    else:
        caches = {"scan": stack(n_scan, one_cache())}
    if cfg.first_dense_layers:
        dense_cfg = dataclasses.replace(cfg, n_experts=0)
        caches["dense"] = [
            (Lyr.init_mla_cache(dense_cfg, batch, max_len, dtype)
             if cfg.is_mla else
             Lyr.init_attention_cache(dense_cfg, batch, max_len, dtype))
            for _ in range(cfg.first_dense_layers)]
    return _place_caches(cfg, caches, tpctx)


def init_paged_caches(cfg: ModelConfig, batch: int, n_pages: int,
                      page_size: int, dtype, tpctx=None, kv_dtype=None):
    """Paged variant of :func:`init_caches`: every layer's KV cache is a
    pool of ``n_pages`` fixed-size pages instead of a contiguous
    ``(batch, max_len)`` slab, so cache memory scales with resident tokens,
    not worst-case length (docs/serving.md). One ``(batch, n_blocks)``
    block table — passed per call via ``batch["block_tables"]`` — addresses
    every layer's pool identically (each layer writes the same logical
    positions), the vLLM layout.

    Covers the GQA/MQA attention families only: SSD/conv recurrent state
    has no positions to page, and the MLA latent cache stays contiguous.

    With ``tpctx`` each model shard owns its slice of every page pool —
    the (P, page_size, Hkv, dh) tensors shard on the KV-head dim, so the
    paged kernel reads/writes only its own heads' pages per shard while
    the host-side PagePool accounting (logical pages, identical on every
    shard) stays unchanged (docs/serving.md).

    ``kv_dtype="int8"`` stores every pool int8 with per-page-per-head fp32
    scale side-tensors (docs/quant.md#kv-pages); under ``tpctx`` the
    scales shard on their KV-head dim alongside the pools.
    """
    if cfg.family in ("ssm", "hybrid") or cfg.attn_every:
        raise NotImplementedError(
            f"paged KV caches require pure-attention layer stacks; family="
            f"{cfg.family!r} attn_every={cfg.attn_every} carries SSD "
            f"recurrent state (docs/serving.md)")
    if cfg.is_mla:
        raise NotImplementedError(
            "paged KV caches cover GQA attention; the MLA latent cache "
            "stays contiguous (docs/serving.md)")
    n_scan = cfg.n_layers - cfg.first_dense_layers

    def stack(n, tree):
        return jax.tree_util.tree_map(
            lambda t: jnp.broadcast_to(t[None], (n,) + t.shape).copy(), tree)

    caches = {"scan": stack(n_scan, Lyr.init_paged_attention_cache(
        cfg, batch, n_pages, page_size, dtype, kv_dtype=kv_dtype))}
    if cfg.first_dense_layers:
        dense_cfg = dataclasses.replace(cfg, n_experts=0)
        caches["dense"] = [
            Lyr.init_paged_attention_cache(dense_cfg, batch, n_pages,
                                           page_size, dtype,
                                           kv_dtype=kv_dtype)
            for _ in range(cfg.first_dense_layers)]
    return _place_caches(cfg, caches, tpctx)


# ---------------------------------------------------------------------------
# Losses / steps (pure functions; launch/ wraps them in pjit)
# ---------------------------------------------------------------------------

def lm_loss(params, cfg: ModelConfig, batch, *, aux_weight: float = 0.01):
    logits, _, aux = forward(params, cfg, batch, caches=None)
    tokens = batch["tokens"]
    if cfg.n_codebooks:
        labels = tokens[:, 1:, :]
        lg = logits[:, :-1]
        logp = jax.nn.log_softmax(lg.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)
        loss = jnp.mean(nll)
    else:
        labels = batch.get("labels")
        # vlm: image embeds occupy the first positions; only text predicts
        n_img = (batch["embeds"].shape[1]
                 if batch.get("embeds") is not None else 0)
        if labels is None:
            labels = tokens[:, 1:]
            lg = logits[:, n_img:-1]
        else:
            lg = logits[:, n_img:]
        logp = jax.nn.log_softmax(lg.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        loss = jnp.mean(nll)
    return loss + aux_weight * aux, {"ce": loss, "aux": aux}


# ---------------------------------------------------------------------------
# BERT / ViT (the paper's own evaluation models)
# ---------------------------------------------------------------------------

def bert_config(variant: str) -> ModelConfig:
    dims = {"medium": (8, 512, 8), "base": (12, 768, 12),
            "large": (24, 1024, 16)}[variant]
    L, d, h = dims
    return ModelConfig(
        name=f"bert-{variant}", family="bert", n_layers=L, d_model=d,
        n_heads=h, n_kv_heads=h, d_ff=4 * d, vocab=30522, causal=False,
        mlp_act="gelu", norm="layernorm", source="arXiv:1810.04805")


def vit_config(variant: str) -> ModelConfig:
    dims = {"base": (12, 768, 12, 197), "large": (24, 1024, 16, 197),
            "huge": (32, 1280, 16, 257)}[variant]
    L, d, h, seq = dims
    return ModelConfig(
        name=f"vit-{variant}", family="vit", n_layers=L, d_model=d,
        n_heads=h, n_kv_heads=h, d_ff=4 * d, vocab=1000, causal=False,
        mlp_act="gelu", norm="layernorm", source="arXiv:2010.11929")


def encoder_forward(params, cfg: ModelConfig, batch):
    """BERT/ViT: bidirectional encoder; ViT consumes stubbed patch embeds."""
    logits, _, _ = forward(params, cfg, batch, caches=None, remat=False)
    return logits
