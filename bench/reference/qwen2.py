"""Plain reference of the Qwen2 decoder (arXiv:2407.10671; Hugging Face
``Qwen2ForCausalLM``), in float32 at the highest matmul precision.

Nothing of the program is imported and nothing it made is used: the
weights are the benchmark's own (``bench/model.py``), in the program's
layout. The forward is the published one:

    h = embed[tokens]
    per layer:  x = rmsnorm(h) * g_attn
                q, k, v = x Wq + bq, x Wk + bk, x Wv + bv      (GQA)
                q, k = rope(q), rope(k)       (rotate-half, theta from config)
                h += softmax(q k^T / sqrt(dh), causal) v  Wo
                x = rmsnorm(h) * g_mlp
                h += (silu(x Wg) * (x Wu)) Wd                  (wi = [Wg|Wu])
    logits = rmsnorm(h) * g_final  Whead

Departure, as the configuration file records: the published 1.5B model
ties its head to the embedding; the program keeps a separate head, so the
reference uses the benchmark's separate ``head`` weight too.

Attention runs over query blocks so a 4k-token sequence fits one chip.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

EPS = 1e-6
Q_BLOCK = 512


def _rms(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * scale


def _rope(x, pos, theta):
    """x: (S, H, dh); rotate-half RoPE at positions ``pos`` (S,)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * inv                # (S, d/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v):
    """Causal GQA attention. q: (S, H, dh); k, v: (S, Hkv, dh)."""
    S, H, dh = q.shape
    g = H // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    kpos = jnp.arange(S)
    out = []
    for b in range(0, S, Q_BLOCK):
        qb = q[b:b + Q_BLOCK]
        s = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(jnp.float32(dh))
        qpos = b + jnp.arange(qb.shape[0])
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v))
    return jnp.concatenate(out, 0)


def _layer(h, lw, pos, *, H, Hkv, dh, theta):
    f32 = functools.partial(jax.tree_util.tree_map,
                            lambda t: t.astype(jnp.float32))
    lw = f32(lw)
    a = lw["attn"]
    x = _rms(h, lw["attn_norm"]["scale"])
    q = x @ a["wq"] + a["bq"]
    k = x @ a["wk"] + a["bk"]
    v = x @ a["wv"] + a["bv"]
    S = h.shape[0]
    q, k, v = (q.reshape(S, H, dh), k.reshape(S, Hkv, dh),
               v.reshape(S, Hkv, dh))
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    h = h + _attention(q, k, v).reshape(S, H * dh) @ a["wo"]
    x = _rms(h, lw["mlp_norm"]["scale"])
    gate, up = jnp.split(x @ lw["mlp"]["wi"], 2, axis=-1)
    return h + (jax.nn.silu(gate) * up) @ lw["mlp"]["wo"]


def hidden(w: Dict, raw: Dict, tokens):
    """Final normed hidden states (S, D) of one sequence ``tokens`` (S,)."""
    H, Hkv = raw["num_attention_heads"], raw["num_key_value_heads"]
    dh = raw.get("head_dim") or raw["hidden_size"] // H
    layer = functools.partial(_layer, H=H, Hkv=Hkv, dh=dh,
                              theta=float(raw["rope_theta"]))
    pos = jnp.arange(tokens.shape[0])
    with jax.default_matmul_precision("highest"):
        h = w["embed"][tokens].astype(jnp.float32)
        h, _ = jax.lax.scan(lambda c, lw: (layer(c, lw, pos), None), h,
                            w["layers"])
        return _rms(h, w["final_norm"]["scale"].astype(jnp.float32))


def logits(w: Dict, h_rows):
    """Logits (n, V) of hidden rows (n, D)."""
    with jax.default_matmul_precision("highest"):
        return h_rows @ w["head"].astype(jnp.float32)
