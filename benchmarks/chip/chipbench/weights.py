"""Seeded weights and data, made by the benchmark and not by the program.

The weights fill the program's parameter tree (its layout, from
``jax.eval_shape`` of ``model.init``) with values drawn here from the
seed, in one jitted call on the device, in the dtype each leaf is served
in. The same call, made again after the window, gives the plain
reference the identical weights. The rules follow the published inits
where they matter for scale (Mamba-2's ``A_log``, ``dt_bias`` and ``D``;
fan-in scaled projections); norm scales and biases are drawn around
their usual values so that they are exercised too.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A PRNG key from any non-negative seed up to 64 bits."""
    if seed < 0 or seed >= 2 ** 64:
        raise ValueError(f"seed must lie in [0, 2**64), got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def _path_names(path) -> tuple:
    return tuple(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def _leaf(key, names: tuple, shape: tuple, dtype):
    per = shape[1:] if names[0] == "blocks" else shape
    name = names[-1]
    normal = lambda: jax.random.normal(key, shape, jnp.float32)
    uniform = lambda lo, hi: jax.random.uniform(key, shape, jnp.float32, lo, hi)
    if name in ("embed", "unembed"):
        x = 0.02 * normal()
    elif name in ("scale", "norm_scale"):
        x = 1.0 + 0.1 * normal()
    elif name == "A_log":
        x = jnp.log(uniform(1.0, 16.0))
    elif name == "D":
        x = uniform(0.5, 1.5)
    elif name == "dt_bias":
        dt = jnp.exp(uniform(math.log(1e-3), math.log(1e-1)))
        x = dt + jnp.log(-jnp.expm1(-dt))          # softplus^-1(dt)
    elif name.endswith("_b"):
        x = 0.1 * normal()
    elif name.startswith("conv_"):
        bound = 1.0 / math.sqrt(per[0])             # depthwise, fan-in = width
        x = uniform(-bound, bound)
    elif name in ("wo", "w_out"):
        x = normal() / math.sqrt(math.prod(per[:-1]))
    else:
        x = normal() / math.sqrt(per[0])
    return x.astype(dtype)


def full_params_fn(model):
    """``fn(key) -> params``: the whole parameter tree in the program's
    layout, jitted (one program on the device)."""
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def make(key):
        leaves = [_leaf(jax.random.fold_in(key, i), _path_names(p), s.shape, s.dtype)
                  for i, (p, s) in enumerate(flat)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(make)


def split_full(params: dict, split: int, tie_embeddings: bool):
    """``(frozen, trainable)`` of a full tree, cut at block ``split`` the way
    the program's tier split takes them: the embedding and blocks below
    the split are frozen; the blocks above, the final norm and the output
    head (a copy of the embedding when tied) are trained."""
    blocks = params["blocks"]
    frozen = {"embed": params["embed"],
              "blocks": jax.tree.map(lambda x: x[:split], blocks)}
    trainable = {"blocks": jax.tree.map(lambda x: x[split:], blocks),
                 "final_norm": params["final_norm"],
                 "unembed": params["embed"] if tie_embeddings else params["unembed"]}
    return frozen, trainable


def token_rows(seed: int, n_rows: int, seq: int, vocab: int) -> np.ndarray:
    """``n_rows`` distinct rows of token ids in ``[0, vocab)``, from the seed."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, vocab, (n_rows, seq), dtype=np.int32)
