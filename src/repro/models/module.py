"""Minimal param-pytree module system (no external NN library).

Conventions:
  * Params are plain nested dicts of ``jnp.ndarray``.
  * Every layer is a pair of pure functions ``init(key, cfg, ...) -> params``
    and ``apply(params, x, ...) -> y``.
  * Layer stacks are *stacked* pytrees (leading axis = block index) consumed
    by ``jax.lax.scan`` — this keeps HLO size O(1) in depth, which matters
    for 40 dry-run compiles of up-to-80-layer models.
  * Storage dtype (``param_dtype``) and compute dtype are decoupled; params
    are cast on entry to each block.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {
    "float32": jnp.float32,
    "bfloat16": jnp.bfloat16,
    "float16": jnp.float16,
    "int8": jnp.int8,
}


def dtype_of(name: str):
    return DTYPES[name]


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------
def dense_init(key, in_dim: int, out_shape, dtype) -> jnp.ndarray:
    """Fan-in scaled normal init (LeCun)."""
    shape = (in_dim,) + tuple(np.atleast_1d(out_shape).tolist())
    scale = 1.0 / np.sqrt(in_dim)
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def embed_init(key, vocab: int, dim: int, dtype) -> jnp.ndarray:
    return (jax.random.normal(key, (vocab, dim)) * 0.02).astype(dtype)


def zeros_init(shape, dtype) -> jnp.ndarray:
    return jnp.zeros(shape, dtype)


def ones_init(shape, dtype) -> jnp.ndarray:
    return jnp.ones(shape, dtype)


# ---------------------------------------------------------------------------
# Stacking helpers
# ---------------------------------------------------------------------------
def stack_init(init_fn: Callable, key, n: int):
    """Initialize ``n`` copies of a block and stack leaves on a leading axis.

    ``init_fn(key_i, i)`` must return the per-block param pytree.
    """
    keys = jax.random.split(key, n)
    idx = jnp.arange(n)
    return jax.vmap(init_fn)(keys, idx)


def slice_stack(stacked, lo: int, hi: int):
    """Static slice of a stacked param tree: blocks [lo, hi)."""
    return jax.tree.map(lambda x: x[lo:hi], stacked)


def stack_len(stacked) -> int:
    leaves = jax.tree.leaves(stacked)
    return int(leaves[0].shape[0]) if leaves else 0


def cast_tree(tree, dtype):
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        tree,
    )


def tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def tree_param_count(tree) -> int:
    return sum(x.size for x in jax.tree.leaves(tree))


# ---------------------------------------------------------------------------
# Rematerialization
# ---------------------------------------------------------------------------
def remat(fn):
    """``fn`` checkpointed with nothing saved: its backward recomputes
    the forward of each scanned block."""
    return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable,
                          prevent_cse=False)
