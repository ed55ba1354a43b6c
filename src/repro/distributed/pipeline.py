"""GPipe-style pipeline parallelism over the 'pod' mesh axis (optional
strategy, DESIGN.md §6).

The layer stack is split into ``n_stages`` contiguous stages; stage s lives
on pod s (weights sharded P('pod') on the stage axis inside shard_map).
Microbatches flow through stages with ``ppermute`` transfers; the classic
GPipe schedule runs M microbatches over S stages in (M + S - 1) ticks with
bubble fraction (S-1)/(M+S-1).

This module is deliberately model-agnostic: it pipelines any
``layer_fn(params_stage, x) -> x``.  An integration test drives a 2-stage ×
2-device CPU mesh; the dry-run exercises 2 pods × 256.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def pipeline_apply(layer_fn, stage_params, x_microbatches, *, axis: str = "pod"):
    """Run inside shard_map: stage_params holds THIS pod's stage weights;
    x_microbatches: (M, mb, ...) microbatch queue (replicated content).

    Returns the final-stage outputs for every microbatch (valid on the last
    stage; other stages return the in-flight values).
    """
    n_stages = lax.axis_size(axis)
    stage = lax.axis_index(axis)
    M = x_microbatches.shape[0]
    ticks = M + n_stages - 1

    perm = [(i, i + 1) for i in range(n_stages - 1)]  # stage i -> i+1

    def tick(carry, t):
        state, outputs = carry  # state: (mb, ...) current in-flight value
        # stage 0 injects microbatch t (when t < M); others use received state
        inject = x_microbatches[jnp.minimum(t, M - 1)]
        x_in = jnp.where(stage == 0, inject, state)
        y = layer_fn(stage_params, x_in)
        # shift: stage s sends y to s+1
        received = lax.ppermute(y, axis, perm)
        # last stage records its output for microbatch (t - (S-1))
        out_idx = t - (n_stages - 1)
        is_valid = (stage == n_stages - 1) & (out_idx >= 0)
        outputs = lax.cond(
            is_valid,
            lambda o: lax.dynamic_update_index_in_dim(
                o, y, jnp.maximum(out_idx, 0), axis=0),
            lambda o: o,
            outputs)
        return (received, outputs), None

    state0 = jnp.zeros_like(x_microbatches[0])
    outputs0 = jnp.zeros_like(x_microbatches)
    (state, outputs), _ = lax.scan(tick, (state0, outputs0),
                                   jnp.arange(ticks))
    return outputs


def make_pipelined_fn(layer_fn, mesh, *, axis: str = "pod",
                      stage_param_spec=P("pod"), x_spec=P()):
    """shard_map wrapper: stage weights sharded over ``axis``; microbatches
    replicated in, final outputs taken from the last stage."""
    def fn(stage_params, xs):
        out = pipeline_apply(layer_fn, stage_params, xs, axis=axis)
        # broadcast final-stage outputs to all stages for a replicated
        # return (mask + psum: ppermute can't fan out one source to many)
        n = lax.axis_size(axis)
        last = (lax.axis_index(axis) == n - 1).astype(out.dtype)
        return lax.psum(out * last, axis)

    return jax.shard_map(fn, mesh=mesh,
                         in_specs=(stage_param_spec, x_spec),
                         out_specs=x_spec, check_vma=False)


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)
