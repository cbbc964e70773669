"""Train steps: Hapi==baseline semantics, accumulation invariance,
frozen-prefix immutability, convergence on a fixed batch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_batch, smoke_model
from repro.config import RunConfig, ShapeConfig, TrainConfig
from repro.core.splitter import SplitDecision
from repro.core.tier_split import TierPlan
from repro.train.steps import (
    build_baseline_train_step,
    build_hapi_train_step,
    build_tier_steps,
    init_train_state,
)


def _setup(arch, micro=4, cos=4, split=1, seq=32, batch=8, compress=False):
    cfg, model, _ = smoke_model(arch)
    shape = ShapeConfig("t", "train", seq, batch)
    rc = RunConfig(model=cfg, shape=shape,
                   train=TrainConfig(microbatch=micro, total_steps=20,
                                     warmup_steps=2))
    plan = TierPlan(split, cos, compress, SplitDecision(split, 0, 0, [], "t"))
    state = init_train_state(model, rc, plan, jax.random.PRNGKey(0))
    batch_d = make_batch(cfg, batch=batch, seq=seq)
    return cfg, model, rc, plan, state, batch_d


@pytest.mark.parametrize("arch", ["gemma2-9b", "jamba-v0.1-52b"])
def test_hapi_equals_baseline_first_step(arch):
    cfg, model, rc, plan, state, batch = _setup(arch)
    s1, m1 = jax.jit(build_hapi_train_step(model, rc, plan))(state, batch)
    state2 = init_train_state(model, rc, plan, jax.random.PRNGKey(0))
    s2, m2 = jax.jit(build_baseline_train_step(model, rc, plan.split))(state2, batch)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-3
    # parameter updates agree (same grads up to accumulation averaging)
    for a, b in zip(jax.tree.leaves(s1.trainable), jax.tree.leaves(s2.trainable)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=5e-3)


def test_accumulation_chunking_invariance():
    """Chunked grad accumulation == one-shot full-batch gradients."""
    cfg, model, rc, plan, state, batch = _setup("mistral-nemo-12b", micro=2, cos=2)
    s1, m1 = jax.jit(build_hapi_train_step(model, rc, plan))(state, batch)
    cfg2, model2, rc2, plan2, state2, _ = _setup("mistral-nemo-12b", micro=8, cos=8)
    s2, m2 = jax.jit(build_hapi_train_step(model2, rc2, plan2))(state2, batch)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4
    for a, b in zip(jax.tree.leaves(s1.trainable), jax.tree.leaves(s2.trainable)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=5e-3)


@pytest.mark.parametrize("compress", [False, True])
def test_fused_coarse_and_tier_paths_agree(compress):
    """The fused step (extract inside the accumulation scan), the coarse
    step (one extract at a COS batch above the microbatch) and the two
    tier programs composed by hand take the same step on one batch."""
    _, model, rc, plan, state, batch = _setup("mistral-nemo-12b", micro=4, cos=2,
                                              compress=compress)
    s1, m1 = jax.jit(build_hapi_train_step(model, rc, plan))(state, batch)
    _, model2, rc2, plan2, state2, _ = _setup("mistral-nemo-12b", micro=2, cos=4,
                                              compress=compress)
    s2, m2 = jax.jit(build_hapi_train_step(model2, rc2, plan2))(state2, batch)
    extract_step, tune_step = build_tier_steps(model2, rc2, plan2)
    acts = jax.jit(extract_step)(state2.frozen, batch)
    t3, _, m3 = jax.jit(tune_step)(state2.trainable, state2.opt, acts, batch)
    for m, trainable in ((m2, s2.trainable), (m3, t3)):
        assert abs(float(m1["loss"]) - float(m["loss"])) < 1e-4
        for a, b in zip(jax.tree.leaves(s1.trainable), jax.tree.leaves(trainable)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32), atol=5e-3)


def test_frozen_prefix_immutable_and_loss_decreases():
    cfg, model, rc, plan, state, batch = _setup("qwen3-32b")
    step = jax.jit(build_hapi_train_step(model, rc, plan))
    frozen0 = jax.tree.map(np.asarray, state.frozen)
    losses = []
    for _ in range(8):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]
    for a, b in zip(jax.tree.leaves(state.frozen), jax.tree.leaves(frozen0)):
        np.testing.assert_array_equal(np.asarray(a), b)


def test_opt_step_counts():
    cfg, model, rc, plan, state, batch = _setup("mamba2-1.3b")
    step = jax.jit(build_hapi_train_step(model, rc, plan))
    state, _ = step(state, batch)
    state, _ = step(state, batch)
    assert int(state.opt.step) == 2
