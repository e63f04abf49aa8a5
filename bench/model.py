"""A configuration file as the program runs it: the program's model config,
weights made on the device from the seed, and the serving engine.

A configuration file (``bench/configs/<name>.json``) holds the model's
published ``config.json`` keys, the keys this benchmark changed (listed in
``reduced`` of ``BENCHMARK.json``) and a ``serving`` group: the deployment
the cell stands for (slots, context, pool, prefill chunk, chips). The
program has no option for some published values (its RMSNorm epsilon, its
activation); they are checked here and a file that asks for another value
is refused, so a run never departs from what its file states.
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Dict

import jax
import jax.numpy as jnp

# The program's fixed choices: a config that states otherwise cannot run.
_PROGRAM_FIXED = {"hidden_act": "silu", "rms_norm_eps": 1e-6}
# spread of the attention scores q.k/sqrt(dh) that the random weights give
QK_GAIN = 3.0
_FAMILIES = {"qwen2": {"qkv_bias": True, "qk_norm": False}}


def load_config(root: Path, entry: Dict) -> Dict:
    return json.loads((root / entry["file"]).read_text())


def program_config(raw: Dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.models.config import ModelConfig

    for key, want in _PROGRAM_FIXED.items():
        if raw.get(key, want) != want:
            raise ValueError(f"{raw['name']}: {key}={raw[key]!r}, but the "
                             f"program only implements {want!r}")
    if raw.get("tie_word_embeddings", False):
        raise ValueError(f"{raw['name']}: the program keeps a separate LM "
                         f"head; tied embeddings cannot run")
    family = _FAMILIES[raw["model_type"]]
    if raw.get("attention_bias", family["qkv_bias"]) != family["qkv_bias"]:
        raise ValueError(f"{raw['name']}: attention_bias differs from the "
                         f"{raw['model_type']} family")
    return ModelConfig(
        name=raw["name"], family="dense",
        n_layers=raw["num_hidden_layers"], d_model=raw["hidden_size"],
        n_heads=raw["num_attention_heads"],
        n_kv_heads=raw["num_key_value_heads"],
        d_head=raw.get("head_dim"), d_ff=raw["intermediate_size"],
        vocab=raw["vocab_size"], rope_theta=float(raw["rope_theta"]),
        dtype=raw["torch_dtype"], **family)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits (``jax.random.key`` keeps
    only the low 32)."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def weight_shapes(raw: Dict) -> Dict:
    """Shapes and init scales of every weight, in the program's parameter
    layout: layers stacked on a leading axis, ``wi`` = [gate | up]."""
    D, F, V = raw["hidden_size"], raw["intermediate_size"], raw["vocab_size"]
    L, H = raw["num_hidden_layers"], raw["num_attention_heads"]
    Hkv = raw["num_key_value_heads"]
    dh = raw.get("head_dim") or D // H
    fam = _FAMILIES[raw["model_type"]]
    # (shape, kind): "w" ~ N(0, 1/fan_in), "e" ~ N(0, 1), "b" ~ N(0, 0.1^2),
    # "n" ~ 1 + N(0, 0.1^2) (norm scales); "qk" is "w" times sqrt(QK_GAIN),
    # so that attention scores have a spread of about QK_GAIN and a head
    # attends to a few positions, not to all alike
    attn = {"wq": ((L, D, H * dh), "qk"), "wk": ((L, D, Hkv * dh), "qk"),
            "wv": ((L, D, Hkv * dh), "w"), "wo": ((L, H * dh, D), "w")}
    if fam["qkv_bias"]:
        attn.update(bq=((L, H * dh), "b"), bk=((L, Hkv * dh), "b"),
                    bv=((L, Hkv * dh), "b"))
    return {
        "embed": ((V, D), "e"),
        "layers": {"attn_norm": {"scale": ((L, D), "n")}, "attn": attn,
                   "mlp_norm": {"scale": ((L, D), "n")},
                   "mlp": {"wi": ((L, D, 2 * F), "w"),
                           "wo": ((L, F, D), "w")}},
        "final_norm": {"scale": ((D,), "n")},
        "head": ((D, V), "w"),
    }


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def init_weights(raw: Dict, seed: int) -> Dict:
    """Every weight, in one jitted call on the device, in the type it is
    served in."""
    spec = weight_shapes(raw)
    dtype = jnp.dtype(raw["torch_dtype"])
    leaves, treedef = jax.tree_util.tree_flatten(spec, is_leaf=_is_spec)

    def init(key):
        out = []
        for i, (shape, kind) in enumerate(leaves):
            k = jax.random.fold_in(key, i)
            x = jax.random.normal(k, shape, jnp.float32)
            if kind in ("w", "qk"):
                x = x / math.sqrt(shape[-2])
            elif kind == "b":
                x = 0.1 * x
            elif kind == "n":
                x = 1.0 + 0.1 * x
            if kind == "qk":
                x = x * math.sqrt(QK_GAIN)
            out.append(x.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(init)(seed_key(seed))


def check_layout(cfg, weights) -> None:
    """The weights must have exactly the program's parameter tree."""
    from repro.models import transformer as T

    want = jax.eval_shape(lambda k: T.init_model(k, cfg)[0],
                          jax.random.PRNGKey(0))
    got = jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), weights)
    want = jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), want)
    if got != want:
        raise ValueError(f"weight layout differs from the program's: "
                         f"{got} != {want}")


def make_engine(raw: Dict, seed: int, **lower):
    """The served model: weights from ``seed`` and a ``ServingEngine`` as
    the file's ``serving`` group deploys it (paged pool, prefix cache,
    chunked prefill, GEMM policy ``auto``) on one chip.

    ``lower`` switches on one of the program's own lower-precision paths
    (``weight_dtype``, ``kv_dtype`` of ``ServeConfig``): the control of the
    correctness check, never a benchmark run."""
    from repro.core.plan import AttentionPolicy, GemmPolicy
    from repro.serving.engine import ServeConfig, ServingEngine
    from repro.serving.scheduler import Scheduler

    cfg = program_config(raw)
    srv = raw["serving"]
    weights = init_weights(raw, seed)
    check_layout(cfg, weights)
    sc = ServeConfig(
        batch_slots=srv["batch_slots"], max_len=srv["max_len"],
        cache_dtype=raw["torch_dtype"], gemm=GemmPolicy(),
        attention=AttentionPolicy(backend="paged",
                                  page_size=srv["page_size"]),
        cache_pages=srv["cache_pages"], prefix_cache=True,
        scheduler=Scheduler(prefill_chunk=srv["prefill_chunk"]), **lower)
    return ServingEngine(cfg, weights, sc)
