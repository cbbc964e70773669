"""The split fine-tune program names its phases on the device.

Every ``repro.obs.DEVICE_SCOPES`` name reaches the compiled program's
``op_name`` metadata on each path that builds the step: the fused step
(extract chunk by chunk inside the accumulation scan), the coarse step
(one extract at a COS batch larger than the microbatch) and the
two-program tier split. Every matrix product sits under some phase, so
a trace of the step leaves no model FLOPs outside the named phases.
"""
import re

import jax
import pytest

from conftest import make_batch, smoke_model
from repro.config import RunConfig, ShapeConfig, TrainConfig
from repro.core.splitter import SplitDecision
from repro.core.tier_split import TierPlan
from repro.obs import DEVICE_SCOPES, device_scope
from repro.train.steps import build_hapi_train_step, build_tier_steps, init_train_state

OP_NAME = re.compile(r'op_name="([^"]*)"')
PHASE = re.compile(r"(?:^|[/(])(hapi\.[a-z_]+)(?=[)/:]|$)")
MATMUL = re.compile(r"^\s*(?:ROOT\s+)?%\S+ = .*? (?:dot|convolution)\(")


def _compiled_texts(path: str):
    """The compiled HLO text of the step's program(s) for one path, at
    the smoke width, with the int8 boundary on."""
    cfg, model, _ = smoke_model("mistral-nemo-12b")
    micro, cos = {"fused": (4, 2), "coarse": (2, 4), "tier": (4, 4)}[path]
    rc = RunConfig(model=cfg, shape=ShapeConfig("t", "train", 32, 8),
                   train=TrainConfig(microbatch=micro, total_steps=20, warmup_steps=2))
    plan = TierPlan(1, cos, True, SplitDecision(1, 0, 0, [], "t"))
    state = init_train_state(model, rc, plan, jax.random.PRNGKey(0))
    batch = make_batch(cfg, batch=8, seq=32)
    if path != "tier":
        step = jax.jit(build_hapi_train_step(model, rc, plan))
        return [step.lower(state, batch).compile().as_text()]
    extract_step, tune_step = build_tier_steps(model, rc, plan)
    ex = jax.jit(extract_step).lower(state.frozen, batch)
    acts = jax.eval_shape(extract_step, state.frozen, batch)
    tu = jax.jit(tune_step).lower(state.trainable, state.opt, acts, batch)
    return [ex.compile().as_text(), tu.compile().as_text()]


@pytest.mark.parametrize("path", ["fused", "coarse", "tier"])
def test_every_phase_is_named_and_every_matmul_is_in_one(path):
    texts = _compiled_texts(path)
    phases = {p for t in texts for name in OP_NAME.findall(t)
              for p in PHASE.findall(name)}
    assert phases == DEVICE_SCOPES
    matmuls = [line for t in texts for line in t.splitlines() if MATMUL.match(line)]
    assert matmuls
    outside = [line[:200] for line in matmuls
               if not any(PHASE.search(n) for n in OP_NAME.findall(line))]
    assert not outside, outside


def test_device_scope_refuses_an_unregistered_name():
    with pytest.raises(ValueError, match="DEVICE_SCOPES"):
        device_scope("hapi.unknown")
    with device_scope("hapi.tune"):
        pass
