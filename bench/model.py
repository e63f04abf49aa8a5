"""A configuration file as the program runs it: the program's model config,
weights made on the device from the seed, and the serving engine, on one
chip or tensor-parallel over the cell's chips.

A configuration file (``bench/configs/<name>.json``) holds the model's
published ``config.json`` keys, the keys this benchmark changed (listed in
``reduced`` of ``BENCHMARK.json``) and a ``serving`` group: the deployment
the cell stands for (slots, context, pool, prefill chunk, chips). The
program has no option for some published values (its RMSNorm epsilon, its
activation); they are checked here and a file that asks for another value
is refused, so a run never departs from what its file states.

What the file's ``model_type`` means to the program is in a family file,
``bench/families/<model_type>.py``, found by name as references and
metric readers are. It gives:

- ``PROGRAM``: the ``ModelConfig`` keys that the model type fixes, beside
  the decoder keys every configuration file states;
- optionally ``config(raw)``: further ``ModelConfig`` keys read from the
  file's published keys (experts, latent ranks), or a refusal of a value
  the program cannot run;
- optionally ``init_kind(path, shape)``: how each weight is drawn, where
  :func:`init_kind` does not fit;
- optionally ``layer_matmuls(raw)`` and ``kv_bytes_per_token(raw,
  itemsize)``: the per-layer work ``bench/flops.py`` counts, where the
  dense count does not hold.
"""
from __future__ import annotations

import importlib.util
import json
import math
from pathlib import Path
from types import ModuleType
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import AxisType

# The program's fixed choices: a config that states otherwise cannot run.
_PROGRAM_FIXED = {"hidden_act": "silu", "rms_norm_eps": 1e-6}
# spread of the attention scores q.k/sqrt(dh) that the random weights give
QK_GAIN = 3.0


def load_config(root: Path, entry: Dict) -> Dict:
    return json.loads((root / entry["file"]).read_text())


def load_module(path: Path) -> ModuleType:
    """A benchmark file that is found by name (a family, a reference, a
    metric reader), loaded from the checkout it belongs to."""
    name = "bench_" + "_".join(path.with_suffix("").parts[-2:])
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_family(root: Path, raw: Dict) -> ModuleType:
    path = root / "bench" / "families" / f"{raw['model_type']}.py"
    if not path.is_file():
        raise ValueError(f"{raw['name']}: no family file {path} for "
                         f"model_type {raw['model_type']!r}")
    return load_module(path)


def program_config(raw: Dict, family: ModuleType):
    """The program's ``ModelConfig`` for a configuration file: the decoder
    keys every file states, then the keys its family fixes or reads."""
    from repro.models.config import ModelConfig

    for key, want in _PROGRAM_FIXED.items():
        if raw.get(key, want) != want:
            raise ValueError(f"{raw['name']}: {key}={raw[key]!r}, but the "
                             f"program only implements {want!r}")
    if raw.get("tie_word_embeddings", False):
        raise ValueError(f"{raw['name']}: the program keeps a separate LM "
                         f"head; tied embeddings cannot run")
    keys = dict(
        name=raw["name"], n_layers=raw["num_hidden_layers"],
        d_model=raw["hidden_size"], n_heads=raw["num_attention_heads"],
        n_kv_heads=raw["num_key_value_heads"], d_head=raw.get("head_dim"),
        d_ff=raw["intermediate_size"], vocab=raw["vocab_size"],
        rope_theta=float(raw["rope_theta"]), dtype=raw["torch_dtype"],
        **family.PROGRAM)
    if hasattr(family, "config"):
        keys.update(family.config(raw))
    bias = keys.get("qkv_bias", False)
    if raw.get("attention_bias", bias) != bias:
        raise ValueError(f"{raw['name']}: attention_bias differs from the "
                         f"{raw['model_type']} family")
    return ModelConfig(**keys)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits (``jax.random.key`` keeps
    only the low 32)."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


class Leaf(NamedTuple):
    shape: Tuple[int, ...]
    dtype: jnp.dtype
    kind: str


def init_kind(path: Tuple[str, ...], shape: Tuple[int, ...]) -> str:
    """How a weight is drawn, from its role (the last key of its path):
    "e" ~ N(0, 1) (the embedding); "n" ~ 1 + N(0, 0.1^2) (norm scales);
    "b" ~ N(0, 0.1^2) (biases: ``bias``, ``bq``, ``bk``, ``bv``, ...);
    "qk" (``wq``, ``wk``) is "w" times sqrt(QK_GAIN), so that attention
    scores have a spread of about QK_GAIN and a head attends to a few
    positions, not to all alike; every other weight is "w" ~ N(0,
    1/fan_in), its fan-in the next-to-last axis."""
    name = path[-1]
    if name == "embed":
        return "e"
    if name == "scale":
        return "n"
    if name == "bias" or (len(name) == 2 and name[0] == "b"):
        return "b"
    return "qk" if name in ("wq", "wk") else "w"


def weight_shapes(cfg, family: ModuleType) -> Dict:
    """Shape, type and init kind of every weight, in the program's own
    parameter tree (its init traced abstractly: no weight is made), so a
    family the program implements needs no tree written out here. A family
    file's ``init_kind(path, shape)`` takes the place of :func:`init_kind`.
    """
    from repro.models import transformer as T

    tree = jax.eval_shape(lambda k: T.init_model(k, cfg)[0],
                          jax.random.PRNGKey(0))
    kind = getattr(family, "init_kind", init_kind)

    def leaf(path, x):
        names = tuple(str(getattr(p, "key", p)) for p in path)
        return Leaf(tuple(x.shape), x.dtype, kind(names, tuple(x.shape)))

    return jax.tree_util.tree_map_with_path(leaf, tree)


def tp_context(cfg, devices):
    """The program's tensor-parallel context over the run's chips, a
    (data=1, model=chips) mesh with the axis types of
    ``repro.launch.mesh``; None on one chip."""
    if len(devices) == 1:
        return None
    from repro.distributed import tp as TP

    mesh = jax.make_mesh((1, len(devices)), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2, devices=devices)
    return TP.make_context(mesh, None, cfg)


def init_weights(cfg, family: ModuleType, seed: int, tp=None) -> Dict:
    """Every weight, in one jitted call on the device, in the type it is
    served in; each leaf from its own ``fold_in`` of the seed's key, by its
    place in the flattened tree. With ``tp`` each weight is made already in
    the program's TP placement (``tp.param_shardings``): each chip makes its
    own shard and none holds the whole model. The values do not depend on
    the number of chips (the PRNG is partitionable)."""
    spec = weight_shapes(cfg, family)
    leaves, treedef = jax.tree_util.tree_flatten(
        spec, is_leaf=lambda x: isinstance(x, Leaf))

    def init(key):
        out = []
        for i, (shape, dtype, kind) in enumerate(leaves):
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

    key = seed_key(seed)
    if tp is None:
        return jax.jit(init)(key)
    from repro.distributed import tp as TP
    from repro.models import transformer as T

    shardings = TP.param_shardings(T.model_axes(cfg),
                                   jax.eval_shape(init, key), tp)
    return jax.jit(init, out_shardings=shardings)(key)


def make_engine(raw: Dict, cfg, family: ModuleType, seed: int,
                tp=None, **lower):
    """The served model: weights from ``seed`` and a ``ServingEngine`` as
    the file's ``serving`` group deploys it (paged pool, prefix cache,
    chunked prefill, GEMM policy ``auto``), tensor-parallel over ``tp``'s
    mesh where there is one.

    ``lower`` switches on one of the program's own lower-precision paths
    (``weight_dtype``, ``kv_dtype`` of ``ServeConfig``): the control of the
    correctness check, never a benchmark run."""
    from repro.core.plan import AttentionPolicy, GemmPolicy
    from repro.serving.engine import ServeConfig, ServingEngine
    from repro.serving.scheduler import Scheduler

    srv = raw["serving"]
    weights = init_weights(cfg, family, seed, tp)
    sc = ServeConfig(
        batch_slots=srv["batch_slots"], max_len=srv["max_len"],
        cache_dtype=raw["torch_dtype"], gemm=GemmPolicy(),
        attention=AttentionPolicy(backend="paged",
                                  page_size=srv["page_size"]),
        cache_pages=srv["cache_pages"], prefix_cache=True,
        scheduler=Scheduler(prefill_chunk=srv["prefill_chunk"]),
        mesh=None if tp is None else tp.mesh, **lower)
    return ServingEngine(cfg, weights, sc)
