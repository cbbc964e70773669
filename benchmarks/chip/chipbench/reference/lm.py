"""The plain reference of a split fine-tune job on a decoder LM.

embedding -> frozen blocks [0, split) -> (int8 boundary) -> trained
blocks [split, n) -> final RMSNorm -> output head over the vocabulary
-> next-token cross-entropy, and AdamW on the trained part. One sequence
at a time, gradients summed over the rows of a step and divided by
their number, so that the reference fits beside nothing else on one
chip. The block itself comes from the architecture's module
(``mamba2.layer``, ``mistral.layer``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import common as C


def leaf_names(tree) -> list:
    return ["/".join(str(getattr(k, "key", k)) for k in p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def leaf_norms(tree) -> dict:
    """Frobenius norm of every leaf, in float64 on the host."""
    norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                               for x in jax.tree.leaves(t)])(tree)
    return dict(zip(leaf_names(tree), (float(n) for n in norms)))


class LMReference:
    """Reference of one configuration; ``arch`` is its block module."""

    def __init__(self, arch, config: dict, precision: str = "f32"):
        self.arch, self.c = arch, config
        self.P = C.Precision(precision)
        c, P = config, self.P
        scale = C.embed_scale(c)
        body = lambda h, lp: (arch.layer(lp, h, c, P), None)

        def prefix(frozen, tokens):
            h = C.embed(frozen["embed"], tokens, scale)
            h, _ = jax.lax.scan(body, h, frozen["blocks"])
            return h

        def suffix_loss(trainable, h, tokens):
            h, _ = jax.lax.scan(jax.checkpoint(body), h, trainable["blocks"])
            h = C.rmsnorm(h, trainable["final_norm"]["scale"], c["norm_eps"])
            logits = P.mm("sd,vd->sv", h, trainable["unembed"][:c["vocab_size"]])
            return C.next_token_loss(logits, tokens)

        self.prefix = jax.jit(prefix)
        self.boundary = jax.jit(C.int8_roundtrip)
        self.loss_grad = jax.jit(jax.value_and_grad(suffix_loss))
        self.add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)
        self.scale = jax.jit(lambda t, s: jax.tree.map(lambda x: x * s, t),
                             donate_argnums=0)

    def train_steps(self, frozen, trainable, batches, traffic: dict,
                    compress: bool, rows=None) -> dict:
        """Run ``len(batches)`` AdamW steps from ``trainable`` (widened to
        float32) on token batches (B, S). ``rows(b)`` may pick the rows a
        step uses (a planted fault). Returns each step's loss, the norm of
        every leaf of the first step's gradient, and of every leaf's change
        over all the steps. The parameters are computed in float32 and
        stored, after each update, in the dtype each leaf is served in (the
        configuration's ``param_dtype``, float32 for Mamba-2's ``A_log``,
        ``D`` and ``dt_bias``); the optimizer's moments stay float32."""
        dtypes = [x.dtype for x in jax.tree.leaves(trainable)]
        store = jax.jit(lambda t: jax.tree.unflatten(jax.tree.structure(t), [
            C.store_as(x, d) for x, d in zip(jax.tree.leaves(t), dtypes)]),
            donate_argnums=0)
        theta = jax.jit(lambda t: jax.tree.map(lambda x: x.astype(jnp.float32), t))(
            trainable)
        zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
        m, v = zeros(theta), zeros(theta)
        adamw = C.adamw_fn(traffic)
        losses, grad_norms = [], None
        for step, toks in enumerate(batches, start=1):
            idx = list(range(toks.shape[0])) if rows is None else rows(toks.shape[0])
            loss_sum, g_sum = 0.0, None
            for r in idx:
                t = jnp.asarray(toks[r])
                h = self.prefix(frozen, t)
                if compress:
                    h = self.boundary(h)
                loss, g = self.loss_grad(theta, h, t)
                loss_sum += float(loss)
                g_sum = g if g_sum is None else self.add(g_sum, g)
            g = self.scale(g_sum, np.float32(1.0 / len(idx)))
            losses.append(loss_sum / len(idx))
            if step == 1:
                grad_norms = leaf_norms(g)
            theta, m, v = adamw(theta, g, m, v, np.float32(step),
                                np.float32(C.lr_at(step, traffic)))
            theta = store(theta)
        delta = jax.jit(lambda a, b: jax.tree.map(
            lambda x, y: x - y.astype(jnp.float32), a, b))(theta, trainable)
        return {"losses": losses, "grad_norms": grad_norms,
                "delta_norms": leaf_norms(delta)}
