"""Production mesh construction.

A function (not a module constant) so importing this module never touches
jax device state — the dry-run must set XLA_FLAGS before first jax init.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis Auto: sharding is propagated by the
    compiler from the constraints the code states (``shard()``, shard_map
    specs). The one place that decides the mesh's axis types."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; multi_pod adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(*, model: int = 1):
    """Whatever devices exist locally, as a (data, model) mesh (CPU tests,
    TP serving on one host).

    ``model`` is the model-axis (tensor-parallel) factor; the data axis
    takes the rest. The old signature silently pinned the model axis to 1
    — callers asking for TP got a mesh that could never shard. Now the
    factor is explicit and an impossible split fails loudly.
    """
    n = len(jax.devices())
    if model < 1:
        raise ValueError(f"model axis factor must be >= 1, got {model}")
    if n % model:
        raise ValueError(
            f"cannot build a (data, model) host mesh: {n} local device(s) "
            f"not divisible by model={model}; pick a factor of {n} (or "
            f"force host devices via XLA_FLAGS="
            f"--xla_force_host_platform_device_count=N before jax init)")
    return make_mesh((n // model, model), ("data", "model"))
