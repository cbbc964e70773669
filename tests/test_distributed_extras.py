"""Elastic re-meshing + pipeline parallelism + tier steps."""
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import HW, MeshSpec, RunConfig, ShapeConfig, TrainConfig
from repro.distributed.elastic import plan_elastic_mesh, reshard_state
from repro.distributed.pipeline import pipeline_bubble_fraction, pipeline_stages

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# Elastic re-meshing
# ---------------------------------------------------------------------------
def test_plan_elastic_shrink():
    ref = MeshSpec((16, 16), ("data", "model"))
    # Lose one pod row: 240 devices -> largest grid with model <= 16.
    ms = plan_elastic_mesh(240, ref)
    assert ms.n_devices == 240
    assert ms.axis_size("model") <= 16
    # Growth: 512 devices, model stays bounded by the reference.
    ms2 = plan_elastic_mesh(512, ref)
    assert ms2.n_devices == 512 and ms2.axis_size("model") <= 16


def test_plan_elastic_respects_hbm():
    ref = MeshSpec((16, 16), ("data", "model"))
    # 1 device cannot hold 100 GB of params.
    ms = plan_elastic_mesh(1, ref, param_bytes=100e9, hbm_budget=16e9)
    assert ms.n_devices == 1  # degenerate fallback still returns a mesh
    # 64 devices can (100/64 < 16).
    ms = plan_elastic_mesh(64, ref, param_bytes=100e9, hbm_budget=16e9)
    assert ms.axis_size("model") * ms.axis_size("data") == 64


def test_reshard_state_single_device():
    from conftest import make_batch, smoke_model
    from repro.core.splitter import SplitDecision
    from repro.core.tier_split import TierPlan
    from repro.train.steps import build_hapi_train_step, init_train_state

    cfg, model, _ = smoke_model("qwen3-32b")
    rc = RunConfig(model=cfg, shape=ShapeConfig("t", "train", 32, 4),
                   train=TrainConfig(microbatch=2))
    plan = TierPlan(1, 2, False, SplitDecision(1, 0, 0, [], "t"))
    state = init_train_state(model, rc, plan, jax.random.PRNGKey(0))

    ms = plan_elastic_mesh(1, MeshSpec((1, 1), ("data", "model")))
    new_state, mesh = reshard_state(state, ms)
    # Training continues on the re-meshed state.
    step = jax.jit(build_hapi_train_step(model, rc, plan))
    batch = make_batch(cfg, batch=4, seq=32)
    new_state, metrics = step(new_state, batch)
    assert np.isfinite(float(metrics["loss"]))


# ---------------------------------------------------------------------------
# Pipeline parallelism (multi-device: subprocess with fake host devices)
# ---------------------------------------------------------------------------
def test_pipeline_bubble_math():
    assert pipeline_bubble_fraction(2, 8) == pytest.approx(1 / 9)
    assert pipeline_bubble_fraction(4, 16) == pytest.approx(3 / 19)


PIPE_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np, functools
    import sys
    sys.path.insert(0, "src")
    from jax.sharding import PartitionSpec as P
    from repro.distributed.pipeline import pipeline_stages

    S, M, D = 4, 8, 16
    mesh = jax.make_mesh((S,), ("stage",))
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (S, D, D)) * 0.3          # one matrix per stage
    x = jax.random.normal(jax.random.fold_in(key, 1), (M, 2, D))

    fn = lambda sp, v: jnp.tanh(v @ sp["w"])
    body = pipeline_stages(fn, S, M, axis="stage")
    piped = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=({"w": P("stage")}, P("stage")),
        out_specs=P(), check_vma=False,
    ))({"w": w}, x)

    # Reference: sequential application of all stages, microbatch order.
    ref = x
    for s in range(S):
        ref = jnp.tanh(ref @ w[s])
    np.testing.assert_allclose(np.asarray(piped), np.asarray(ref), atol=1e-5)
    print("PIPE-OK")
""")


def test_pipeline_four_stage_subprocess():
    r = subprocess.run([sys.executable, "-c", PIPE_PROG], cwd=REPO_ROOT,
                       capture_output=True, text=True, timeout=300)
    assert "PIPE-OK" in r.stdout, r.stdout + r.stderr


# ---------------------------------------------------------------------------
# Tier steps (two-program split used by tierdry)
# ---------------------------------------------------------------------------
def test_tier_steps_match_integrated():
    from conftest import make_batch, smoke_model
    from repro.core.splitter import SplitDecision
    from repro.core.tier_split import TierPlan
    from repro.train.steps import (
        build_hapi_train_step,
        build_tier_steps,
        init_train_state,
    )

    cfg, model, _ = smoke_model("gemma2-9b")
    rc = RunConfig(model=cfg, shape=ShapeConfig("t", "train", 32, 8),
                   train=TrainConfig(microbatch=4))
    plan = TierPlan(1, 4, False, SplitDecision(1, 0, 0, [], "t"))
    state = init_train_state(model, rc, plan, jax.random.PRNGKey(0))
    batch = make_batch(cfg, batch=8, seq=32)

    extract_step, tune_step = build_tier_steps(model, rc, plan)
    acts = jax.jit(extract_step)(state.frozen, batch)
    new_t, new_opt, m2 = jax.jit(tune_step)(state.trainable, state.opt, acts, batch)

    s1, m1 = jax.jit(build_hapi_train_step(model, rc, plan))(state, batch)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4
    for a, b in zip(jax.tree.leaves(s1.trainable), jax.tree.leaves(new_t)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=5e-3)


def test_tier_steps_int8_wire():
    from conftest import make_batch, smoke_model
    from repro.core.splitter import SplitDecision
    from repro.core.tier_split import TierPlan
    from repro.train.steps import build_tier_steps, init_train_state

    cfg, model, _ = smoke_model("mistral-nemo-12b")
    rc = RunConfig(model=cfg, shape=ShapeConfig("t", "train", 32, 8),
                   train=TrainConfig(microbatch=4))
    plan = TierPlan(1, 4, True, SplitDecision(1, 0, 0, [], "t"))
    state = init_train_state(model, rc, plan, jax.random.PRNGKey(0))
    batch = make_batch(cfg, batch=8, seq=32)
    extract_step, tune_step = build_tier_steps(model, rc, plan)
    acts = jax.jit(extract_step)(state.frozen, batch)
    q, scales = acts
    assert q.dtype == jnp.int8
    wire = q.size + scales.size * 4
    dense = q.size * 4  # fp32 smoke activations
    assert wire < 0.6 * dense
    _, _, m = jax.jit(tune_step)(state.trainable, state.opt, acts, batch)
    assert np.isfinite(float(m["loss"]))


def test_serve_driver_smoke():
    from repro.launch.serve import serve

    out = serve("gemma2-9b", batch=2, prompt_len=8, new_tokens=4, smoke=True)
    assert out["tokens"].shape == (2, 5)
    assert out["tok_per_s"] > 0
