"""Pieces every plain reference shares, written from their definitions.

The references import nothing of the program and take nothing that it
has made. They compute in float32 with matrix products at ``HIGHEST``
precision (a float32 product on a TPU is otherwise rounded through
bf16). The weights arrive in the dtype they are served in and are
widened to float32 where they are used, which is exact.

``Precision("fp8")`` is the control: the same reference with every
operand of every matrix product, and every gradient flowing back into
one, rounded to 8-bit floats (4 exponent, 3 mantissa bits) with one
scale per tensor, the step below the bf16 that the configurations state.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
FP8_MAX = 240.0      # largest finite e4m3 value with IEEE-style infinities


def round_to(x, exponent_bits: int, mantissa_bits: int):
    """Round float32 ``x`` to a narrower float format. A pair of casts
    would not do: XLA may drop a convert to a narrower type and back
    (excess precision is allowed by default on TPU)."""
    return jax.lax.reduce_precision(x, exponent_bits=exponent_bits,
                                    mantissa_bits=mantissa_bits)


def store_as(x, dtype):
    """``x`` (float32) rounded to what a ``dtype`` array can hold."""
    fi = jnp.finfo(dtype)
    return round_to(x, fi.nexp, fi.nmant)


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return round_to(x / s, 4, 3) * s


def _bf16(x):
    return round_to(x, 8, 7)


ROUNDING = {"fp8": _fp8, "bf16": _bf16}


def _rounded_product(r):
    """``mm(eq, a, b)`` whose operands, and the gradient flowing back into
    its output, are rounded by ``r``; accumulation stays float32."""

    @jax.custom_vjp
    def round_both(x):
        return r(x)

    round_both.defvjp(lambda x: (r(x), None), lambda _, g: (r(g),))

    @jax.custom_vjp
    def round_grad(x):
        return x

    round_grad.defvjp(lambda x: (x, None), lambda _, g: (r(g),))

    def mm(eq, a, b):
        return round_grad(jnp.einsum(eq, round_both(a), round_both(b), precision=HIGHEST))

    return mm


class Precision:
    """How the reference multiplies matrices: ``"f32"`` (the reference),
    ``"fp8"`` (the control) or ``"bf16"`` (to tell rounding from a fault
    when a number reads high)."""

    def __init__(self, mode: str = "f32"):
        if mode != "f32" and mode not in ROUNDING:
            raise ValueError(mode)
        self.mode = mode
        self._mm = None if mode == "f32" else _rounded_product(ROUNDING[mode])

    def mm(self, eq: str, a, b):
        a, b = a.astype(F32), b.astype(F32)
        if self._mm is not None:
            return self._mm(eq, a, b)
        return jnp.einsum(eq, a, b, precision=HIGHEST)


def rmsnorm(x, scale, eps: float):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(F32)


def int8_roundtrip(x, tile: int = 128):
    """Symmetric int8 with one float32 scale (amax / 127) per ``tile``
    lanes of the last axis, quantized and read back."""
    *lead, d = x.shape
    xt = x.reshape(*lead, d // tile, tile)
    scale = jnp.maximum(jnp.max(jnp.abs(xt), axis=-1, keepdims=True), 1e-8) / 127.0
    q = jnp.clip(jnp.round(xt / scale), -127, 127)
    return (q * scale).reshape(*lead, d)


def next_token_loss(logits, tokens):
    """Mean cross-entropy of predicting ``tokens[t+1]`` from position t.
    logits: (S, V) float32, tokens: (S,)."""
    logp = jax.nn.log_softmax(logits[:-1], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))


def embed(table, tokens, scale: float):
    return table[tokens].astype(F32) * scale


def embed_scale(config: dict) -> float:
    """The configuration states whether the program multiplies the
    embedding by sqrt(d_model) (a departure from both published models)."""
    return math.sqrt(config["d_model"]) if config["departures"]["embed_times_sqrt_d"] \
        else 1.0


# --------------------------------------------------------------------------
# AdamW (Loshchilov & Hutter, arXiv:1711.05101) with global-norm clipping,
# linear warm-up and cosine decay to a floor, as the traffic file states.
# --------------------------------------------------------------------------
def lr_at(step: int, t: dict) -> float:
    warm = min(step / max(t["warmup_steps"], 1), 1.0)
    prog = min(max((step - t["warmup_steps"])
                   / max(t["total_steps"] - t["warmup_steps"], 1), 0.0), 1.0)
    cos = 0.5 * (1 + math.cos(math.pi * prog))
    floor = t["lr_floor_frac"]
    return t["learning_rate"] * warm * (floor + (1 - floor) * cos)


def decay_mask(path_names: tuple, ndim: int) -> float:
    """Weight decay applies to leaves of the stacked tree with rank >= 2
    whose path names no norm, scale, bias or ``ln`` (the traffic file's
    ``weight_decay_mask``)."""
    name = "/".join(path_names)
    if ndim < 2 or any(t in name for t in ("norm", "scale", "bias", "ln")):
        return 0.0
    return 1.0


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(tree)))


def adamw_fn(t: dict):
    """``fn(params, grads, m, v, step, lr) -> (params, m, v)``, jitted;
    ``step`` counts from 1, every leaf is float32."""
    b1, b2, eps, wd, clip_at = (t["beta1"], t["beta2"], t["eps"],
                                t["weight_decay"], t["grad_clip"])

    def step_fn(params, grads, m, v, step, lr):
        clip = jnp.minimum(1.0, clip_at / jnp.maximum(global_norm(grads), 1e-9))
        bc1, bc2 = 1 - b1 ** step, 1 - b2 ** step
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        g_l, m_l, v_l = (jax.tree.leaves(x) for x in (grads, m, v))
        new_p, new_m, new_v = [], [], []
        for (path, p), g, mm, vv in zip(flat, g_l, m_l, v_l):
            names = tuple(str(getattr(k, "key", k)) for k in path)
            g = g * clip
            mm = b1 * mm + (1 - b1) * g
            vv = b2 * vv + (1 - b2) * g * g
            upd = (mm / bc1) / (jnp.sqrt(vv / bc2) + eps) \
                + wd * decay_mask(names, p.ndim) * p
            new_p.append(p - lr * upd)
            new_m.append(mm)
            new_v.append(vv)
        unf = lambda xs: jax.tree_util.tree_unflatten(treedef, xs)
        return unf(new_p), unf(new_m), unf(new_v)

    return jax.jit(step_fn, donate_argnums=(0, 2, 3))
