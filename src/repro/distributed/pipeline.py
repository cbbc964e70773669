"""GPipe-style microbatch pipeline parallelism over a mesh axis.

The paper's tier split IS a 2-stage pipeline (feature extraction |
training); this module provides the general N-stage machinery so deeper
models can spread their *suffix* across pods too (DESIGN.md §5).

SPMD formulation: the layer stack is split into ``n_stages`` contiguous
groups; group i's parameters live on stage-axis shard i. Each pipeline
tick, every stage applies its group to its in-flight microbatch, then the
activations rotate one step along the stage axis with ppermute. After
``n_micro + n_stages - 1`` ticks every microbatch has traversed all
stages (classic GPipe: bubble fraction = (S-1)/(M+S-1)).

The per-stage body is any shape-preserving ``fn(stage_params, x) -> x``
(the residual stream) — exactly our scanned block stacks.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp


def pipeline_stages(
    fn: Callable,             # (stage_params, x) -> x, shape-preserving
    n_stages: int,
    n_micro: int,
    axis: str = "stage",
):
    """Build the shard_map body for an N-stage GPipe pipeline.

    Usage (mesh has an axis named ``axis`` of size n_stages):

        body = pipeline_stages(stage_fn, S, M)
        y = jax.shard_map(body, mesh=mesh,
                          in_specs=(P(axis), P(axis)), out_specs=P(),
                          check_vma=False)(stage_params, micro_x)

    ``stage_params`` leaves have leading dim n_stages (one slice per
    stage); ``micro_x`` has leading dim n_micro, sharded contiguously over
    the stage axis. The result is the full (n_micro, ...) output in
    microbatch order, replicated (the last stage commits; a psum
    broadcasts — at pod scale replace with a reduce-scatter back to the
    data layout).
    """
    assert n_micro % n_stages == 0, (n_micro, n_stages)
    per = n_micro // n_stages
    n_ticks = n_micro + n_stages - 1

    def body(stage_params, micro_x):
        sp = jax.tree.map(lambda p: p[0], stage_params)
        idx = jax.lax.axis_index(axis)
        x_shape = micro_x.shape[1:]
        slot = jnp.zeros(x_shape, micro_x.dtype)
        out = jnp.zeros((n_micro,) + x_shape, micro_x.dtype)

        def tick(carry, t):
            slot, out = carry
            # Stage 0 injects microbatch t (owner shard = t // per).
            owner = t // per
            local = jnp.clip(t % per, 0, per - 1)
            mine = jax.lax.dynamic_index_in_dim(micro_x, local, 0, keepdims=False)
            injected = jax.lax.psum(
                jnp.where(idx == owner, mine, jnp.zeros_like(mine)), axis
            )
            slot = jnp.where(jnp.logical_and(idx == 0, t < n_micro),
                             injected, slot)
            # Every stage applies its layer group.
            y = fn(sp, slot)
            # The last stage commits microbatch t-(S-1).
            done_t = t - (n_stages - 1)
            commit = jnp.logical_and(idx == n_stages - 1, done_t >= 0)
            out = jax.lax.cond(
                commit,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y.astype(o.dtype),
                    jnp.clip(done_t, 0, n_micro - 1), 0),
                lambda o: o,
                out,
            )
            # Rotate activations downstream.
            slot = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)]
            )
            return (slot, out), None

        (slot, out), _ = jax.lax.scan(tick, (slot, out), jnp.arange(n_ticks))
        # Only the last stage wrote; broadcast the result.
        return jax.lax.psum(out, axis)

    return body


def pipeline_bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
