"""Pipeline parallelism: GPipe-style rotational schedule via shard_map.

Each mesh position along the pipeline axis owns one stage (a contiguous
slice of layers). Microbatches enter at stage 0; every tick each stage
applies its layers and ppermutes its activation to the successor; the last
stage collects finished microbatches. ``N + S - 1`` ticks drain N
microbatches through S stages — the standard fill/steady/drain schedule.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.experimental.shard_map import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_layer_stage(layer_fn: Callable) -> Callable:
    """Lift a per-layer fn ``layer_fn(params_i, x) -> x`` into a stage fn
    applying a stacked slice of layers sequentially (scanned)."""

    def stage_fn(stage_params: Any, x: jax.Array) -> jax.Array:
        def body(carry, p):
            return layer_fn(p, carry), None

        out, _ = jax.lax.scan(body, x, stage_params)
        return out

    return stage_fn


def split_stages(layer_params: Any, n_stages: int) -> Any:
    """Reshape layer-stacked params [L, ...] -> [S, L/S, ...]."""

    def split(p):
        L = p.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return p.reshape(n_stages, L // n_stages, *p.shape[1:])

    return jax.tree.map(split, layer_params)


def pipeline_stack(
    stage_fn: Callable,
    stage_params: Any,
    x_micro: jax.Array,
    *,
    mesh: Mesh,
    axis: str,
) -> jax.Array:
    """Run ``x_micro`` [N_micro, ...] through S pipeline stages.

    ``stage_params`` leaves are stage-stacked [S, ...]; stage s lives on
    mesh position s of ``axis``. Returns outputs [N_micro, ...] equal to
    applying all stages sequentially.
    """
    S = mesh.shape[axis]
    N = x_micro.shape[0]
    shift_perm = [(i, (i + 1) % S) for i in range(S)]

    def spmd(params, xs):
        params = jax.tree.map(lambda p: p[0], params)  # local stage slice
        idx = jax.lax.axis_index(axis)
        carry = jnp.zeros(xs.shape[1:], xs.dtype)
        outs = jnp.zeros(xs.shape, xs.dtype)

        def tick(t, state):
            carry, outs = state
            x_in = xs[jnp.minimum(t, N - 1)]
            y = stage_fn(params, jnp.where(idx == 0, x_in, carry))
            out_t = jnp.clip(t - (S - 1), 0, N - 1)
            emit = (idx == S - 1) & (t >= S - 1)
            placed = jax.lax.dynamic_update_slice(
                outs, y[None], (out_t,) + (0,) * (outs.ndim - 1)
            )
            outs = jnp.where(emit, placed, outs)
            carry = jax.lax.ppermute(y, axis, shift_perm)
            return carry, outs

        _, outs = jax.lax.fori_loop(0, N + S - 1, tick, (carry, outs))
        return outs[None]  # [1, N, ...]; valid on the last stage

    result = shard_map(
        spmd,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(axis),
        check_rep=False,
    )(stage_params, x_micro)
    # the last stage's outputs, replicated: an Explicit-axis mesh (the
    # jax.make_mesh default) refuses a plain index into a stage-sharded
    # result without the sharding of what it returns
    return result.at[-1].get(out_sharding=NamedSharding(mesh, P()))
