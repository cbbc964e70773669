"""AdamW with freeze masks, weight-decay masking, warmup-cosine schedule,
configurable state dtype (grok: bf16 states to fit HBM) and ZeRO-2D
sharded states (see distributed/sharding.opt_state_pspecs).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.config import TrainConfig
from repro.models.module import dtype_of
from repro.obs import device_scope


class OptState(NamedTuple):
    m: object
    v: object
    step: jnp.ndarray


def _decay_mask(params):
    """No weight decay on norms/biases/scalars (rank<2 or norm-ish names)."""

    def f(path, x):
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        if x.ndim < 2:
            return 0.0
        if any(t in name for t in ("norm", "scale", "bias", "ln")):
            return 0.0
        return 1.0

    return jax.tree_util.tree_map_with_path(f, params)


def init_opt_state(params, tc: TrainConfig) -> OptState:
    dt = dtype_of(tc.opt_state_dtype)
    zeros = lambda x: jnp.zeros(x.shape, dt)
    return OptState(
        m=jax.tree.map(zeros, params),
        v=jax.tree.map(zeros, params),
        step=jnp.zeros((), jnp.int32),
    )


def lr_schedule(step, tc: TrainConfig):
    warm = jnp.minimum(step / jnp.maximum(tc.warmup_steps, 1), 1.0)
    prog = jnp.clip(
        (step - tc.warmup_steps) / jnp.maximum(tc.total_steps - tc.warmup_steps, 1),
        0.0, 1.0,
    )
    cos = 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return tc.learning_rate * warm * (0.1 + 0.9 * cos)


def global_norm(tree) -> jnp.ndarray:
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(tree))
    )


def adamw_update(params, grads, opt: OptState, tc: TrainConfig):
    """One AdamW step. Returns (new_params, new_opt, metrics)."""
    with device_scope("hapi.adamw"):
        return _adamw_update(params, grads, opt, tc)


def _adamw_update(params, grads, opt: OptState, tc: TrainConfig):
    step = opt.step + 1
    lr = lr_schedule(step, tc)
    gnorm = global_norm(grads)
    clip = jnp.minimum(1.0, tc.grad_clip / jnp.maximum(gnorm, 1e-9)) if tc.grad_clip else 1.0
    decay = _decay_mask(params)
    sdt = dtype_of(tc.opt_state_dtype)

    b1, b2 = tc.beta1, tc.beta2
    bc1 = 1 - b1 ** step.astype(jnp.float32)
    bc2 = 1 - b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v, wd):
        g = g.astype(jnp.float32) * clip
        m32 = b1 * m.astype(jnp.float32) + (1 - b1) * g
        v32 = b2 * v.astype(jnp.float32) + (1 - b2) * jnp.square(g)
        mhat = m32 / bc1
        vhat = v32 / bc2
        delta = mhat / (jnp.sqrt(vhat) + tc.eps) + tc.weight_decay * wd * p.astype(jnp.float32)
        return (
            (p.astype(jnp.float32) - lr * delta).astype(p.dtype),
            m32.astype(sdt),
            v32.astype(sdt),
        )

    out = jax.tree.map(upd, params, grads, opt.m, opt.v, decay)
    new_p = jax.tree.map(lambda t: t[0], out, is_leaf=lambda t: isinstance(t, tuple))
    new_m = jax.tree.map(lambda t: t[1], out, is_leaf=lambda t: isinstance(t, tuple))
    new_v = jax.tree.map(lambda t: t[2], out, is_leaf=lambda t: isinstance(t, tuple))
    metrics = {"lr": lr, "grad_norm": gnorm}
    return new_p, OptState(new_m, new_v, step), metrics
