"""Step builders: Hapi-integrated fine-tune step, status-quo baseline,
prefill and decode. These are the functions the dry-run lowers and the
drivers jit.

The Hapi train step is the paper's pipeline in one program:
  1. extract: frozen prefix at *COS batch* granularity (scan over
     microbatches, stop-gradient, optional int8 boundary compression) —
     §5.5's decoupled batch;
  2. tune: remaining blocks + head, grad-accumulated at *training batch*
     granularity, AdamW on the trainable subtree only.

The baseline step is the paper's status quo: one pass, one batch
granularity, frozen prefix still excluded from grads.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.config import RunConfig
from repro.core.tier_split import TierPlan, make_extract_fn, make_tune_loss_fn
from repro.models.transformer import Model
from repro.obs import device_scope
from repro.optim.adamw import OptState, adamw_update, init_opt_state


class TrainState(NamedTuple):
    frozen: Any        # feature-extraction prefix params (never updated)
    trainable: Any     # suffix params
    opt: OptState


def init_split_params(model: Model, split: int, key):
    """Seeded ``(frozen, trainable)`` params, built in one jitted program
    (eager init of a billion-parameter stack dispatches op by op)."""
    return jax.jit(lambda k: model.split_params(model.init(k), split))(key)


def init_train_state(model: Model, rc: RunConfig, plan: TierPlan, key) -> TrainState:
    frozen, trainable = init_split_params(model, plan.split, key)
    return TrainState(frozen, trainable, init_opt_state(trainable, rc.train))


def _tree_chunk(tree, n_chunks: int):
    return jax.tree.map(
        lambda x: x.reshape(n_chunks, x.shape[0] // n_chunks, *x.shape[1:]), tree
    )


def _micro_chunks(acts, batch, micro: int):
    """``(n_chunks, (acts, batch) split into chunks of ``micro`` rows)``."""
    n_chunks = max(1, next(iter(batch.values())).shape[0] // micro)
    return n_chunks, (_tree_chunk(acts, n_chunks), _tree_chunk(batch, n_chunks))


def _accumulator(tune: Callable, trainable, get_acts: Callable,
                 constrain: Optional[Callable]) -> Callable:
    """``accumulate(xs) -> (grads, loss_sum)``: ``tune``'s loss and its
    gradient in ``trainable``, summed in f32 over a scan of ``xs``, where
    ``get_acts`` maps one element of ``xs`` to ``(acts, batch_chunk)``.
    The zero tree is traced here, so a step calls this where its program
    builds that tree."""
    zeros = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), trainable)
    if constrain:
        zeros = constrain(zeros, "grads")

    def gstep(carry, x):
        g_acc, loss_acc = carry
        acts, bchunk = get_acts(x)
        loss, g = jax.value_and_grad(tune)(trainable, acts, bchunk)
        with device_scope("hapi.tune"):
            g_acc = jax.tree.map(lambda a, b: a + b.astype(a.dtype), g_acc, g)
        if constrain:
            # Keep the accumulator ZeRO-sharded inside the scan carry.
            g_acc = constrain(g_acc, "grads")
        return (g_acc, loss_acc + loss), None

    return lambda xs: jax.lax.scan(gstep, (zeros, 0.0), xs)[0]


def _update(trainable, opt: OptState, grads, loss_sum, n_chunks: int, tc):
    """AdamW on the mean gradient over the chunks: ``(trainable, opt, metrics)``."""
    with device_scope("hapi.adamw"):
        grads = jax.tree.map(lambda g: g / n_chunks, grads)
    new_trainable, new_opt, om = adamw_update(trainable, grads, opt, tc)
    return new_trainable, new_opt, {"loss": loss_sum / n_chunks, **om}


def build_hapi_train_step(
    model: Model,
    rc: RunConfig,
    plan: TierPlan,
    *,
    constrain: Optional[Callable] = None,
) -> Callable:
    """(state, batch) -> (state, metrics). ``constrain(tree, kind)`` may
    apply sharding constraints (kind in {'acts','grads'})."""
    tune = make_tune_loss_fn(model, plan)
    tc = rc.train

    def constrain_acts(acts):
        return constrain(acts, "acts") if constrain else acts

    def train_step(state: TrainState, batch):
        b = next(iter(batch.values())).shape[0]
        cos_b = min(plan.cos_batch, b)          # §5.5: the adapted COS batch
        micro = min(tc.microbatch or b, b)      # grad-accumulation chunk
        extract = make_extract_fn(model, TierPlan(
            plan.split, cos_b, plan.compress, plan.decision))

        if cos_b <= micro:
            # Fused path: extract chunk -> grad on chunk -> accumulate. One
            # chunk's boundary activations live at a time.
            accumulate = _accumulator(
                tune, state.trainable,
                lambda bt: (constrain_acts(extract(state.frozen, bt)), bt), constrain)
            n_chunks = max(1, b // cos_b)
            grads, loss_sum = accumulate(_tree_chunk(batch, n_chunks))
        else:
            # Coarse-extraction path (batch adaptation granted a big COS
            # batch): run feature extraction at cos_b — the frozen-prefix
            # weights are (FSDP-)gathered cos_b/micro times *fewer* — then
            # the tier split's tune step over micro chunks of the activations.
            accumulate = _accumulator(
                tune, state.trainable, lambda ab: (constrain_acts(ab[0]), ab[1]), constrain)
            acts = constrain_acts(extract(state.frozen, batch))
            n_chunks, chunks = _micro_chunks(acts, batch, micro)
            grads, loss_sum = accumulate(chunks)

        trainable, opt, metrics = _update(state.trainable, state.opt, grads, loss_sum,
                                          n_chunks, tc)
        return TrainState(state.frozen, trainable, opt), metrics

    return train_step


def build_baseline_train_step(model: Model, rc: RunConfig, split: int) -> Callable:
    """Status quo (paper Fig. 5a): full model, training-batch granularity,
    grads on the trainable suffix only."""
    tc = rc.train

    def loss_fn(trainable, frozen, batch):
        params = model.merge_params(frozen, trainable, split)
        return model.loss(params, batch)

    def train_step(state: TrainState, batch):
        loss, grads = jax.value_and_grad(loss_fn)(state.trainable, state.frozen, batch)
        new_trainable, new_opt, om = adamw_update(state.trainable, grads, state.opt, tc)
        return TrainState(state.frozen, new_trainable, new_opt), {"loss": loss, **om}

    return train_step


def build_tier_steps(model: Model, rc: RunConfig, plan: TierPlan,
                     *, constrain: Optional[Callable] = None):
    """The two-program tier split (paper Fig. 8): ``extract_step`` runs on
    the storage mesh (COS), ``tune_step`` on the compute mesh; the returned
    activations cross the inter-pod link (optionally int8). ``tune_step``
    is the coarse path of ``build_hapi_train_step`` after its extract.
    """
    tc = rc.train
    extract = make_extract_fn(model, plan)
    tune = make_tune_loss_fn(model, plan)

    def extract_step(frozen, batch):
        return extract(frozen, batch)

    def tune_step(trainable, opt, acts, batch):
        b = next(iter(batch.values())).shape[0]
        n_chunks, chunks = _micro_chunks(acts, batch, min(tc.microbatch or b, b))
        grads, loss_sum = _accumulator(tune, trainable, lambda ab: ab, constrain)(chunks)
        return _update(trainable, opt, grads, loss_sum, n_chunks, tc)

    return extract_step, tune_step


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------
def build_prefill_step(model: Model) -> Callable:
    def prefill_step(params, batch):
        logits, cache = model.prefill(params, batch)
        return logits, cache

    return prefill_step


def build_decode_step(model: Model) -> Callable:
    def serve_step(params, cache, token, pos):
        logits, new_cache = model.decode_step(params, cache, token, pos)
        return logits, new_cache

    return serve_step

