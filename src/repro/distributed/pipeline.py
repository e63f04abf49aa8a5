"""GPipe-style pipeline parallelism over a 'stage' mesh axis.

Off by default (no assigned arch *needs* PP to fit 256 chips — §Dry-run
memory analysis), but provided as a first-class capability for >10B-scale
depth scaling: stages hold contiguous layer slices, microbatches stream
through a shard_map with collective_permute hops between neighbours, and
the classic GPipe bubble (S − 1 of μ + S − 1 slots) amortizes away as μ
grows.

Design notes:
  * params are stacked (S, L/S, ...) and sharded P('stage') on axis 0 —
    each stage's device group holds only its slice (pipeline = depth FSDP);
  * the schedule is a lax.fori_loop over μ + S − 1 ticks; at tick t,
    stage s processes microbatch (t − s) when 0 ≤ t − s < μ;
  * inter-stage transfer is one collective_permute per tick (point-to-point
    neighbour traffic — ICI-cheap, never an all-gather);
  * differentiable end-to-end (jax.grad through shard_map + permute), so
    the same engine serves training; remat composes inside stage_fn.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def stack_for_stages(layer_params, n_stages: int):
    """(L, ...) stacked layer params → (S, L/S, ...) stage-major."""
    def resplit(t):
        L = t.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return t.reshape((n_stages, L // n_stages) + t.shape[1:])
    return jax.tree_util.tree_map(resplit, layer_params)


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params,              # (S, L/S, ...) pytree, sharded P('stage')
    x: jax.Array,              # (μ, mb, ...) microbatched input
    *,
    mesh: Mesh,
    axis: str = "stage",
) -> jax.Array:
    """Run x through S pipeline stages; returns (μ, mb, ...) outputs.

    stage_fn(stage_local_params, x_mb) applies one stage's layer slice to
    one microbatch. The caller supplies microbatched inputs; outputs arrive
    in microbatch order.
    """
    n_stages = mesh.shape[axis]
    mu = x.shape[0]
    n_ticks = mu + n_stages - 1

    def per_stage(params_local, x_local):
        # params_local: (1, L/S, ...) local slice; x_local: full (μ, mb, …)
        # (inputs are replicated; only stage 0 consumes them).
        params_local = jax.tree_util.tree_map(lambda t: t[0], params_local)
        s = jax.lax.axis_index(axis)
        mb_shape = x_local.shape[1:]
        zero_mb = jnp.zeros(mb_shape, x_local.dtype)
        out_buf = jnp.zeros((mu,) + mb_shape, x_local.dtype)

        def tick(t, carry):
            prev_out, out_buf = carry
            # receive neighbour's last output (stage s gets stage s-1's)
            recv = jax.lax.ppermute(
                prev_out, axis,
                [(i, i + 1) for i in range(n_stages - 1)])
            mb_idx = t - s
            active = (mb_idx >= 0) & (mb_idx < mu)
            feed = jnp.where(
                s == 0,
                jax.lax.dynamic_index_in_dim(
                    x_local, jnp.clip(mb_idx, 0, mu - 1), 0, keepdims=False),
                recv)
            y = stage_fn(params_local, feed)
            y = jnp.where(active, y, zero_mb)
            # last stage writes its (t - s)th microbatch output
            write_idx = jnp.clip(mb_idx, 0, mu - 1)
            do_write = active & (s == n_stages - 1)
            cur = jax.lax.dynamic_index_in_dim(out_buf, write_idx, 0,
                                               keepdims=False)
            new = jnp.where(do_write, y, cur)
            out_buf = jax.lax.dynamic_update_index_in_dim(out_buf, new,
                                                          write_idx, 0)
            return (y, out_buf)

        _, out_buf = jax.lax.fori_loop(0, n_ticks, tick, (zero_mb, out_buf))
        # every stage holds a (μ, mb, …) buffer; only the last stage's is
        # real — psum_scatter/broadcast it. Simplest: max over stages (all
        # others are zero) via psum of masked buffer.
        mask = (s == n_stages - 1).astype(out_buf.dtype)
        return jax.lax.psum(out_buf * mask, axis)

    in_specs = (
        jax.tree_util.tree_map(lambda _: P(axis), stage_params),
        P(),                        # microbatches replicated in
    )
    fn = shard_map(per_stage, mesh=mesh, in_specs=in_specs, out_specs=P(),
                   check_vma=False)
    return fn(stage_params, x)
