"""The Pallas kernels and the int8 train step compile for a described TPU
v5e (no chip needed): what interpret-mode tests cannot show — tiling,
layout and VMEM limits — is checked by the TPU compiler itself.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and every
test worker imports this file.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.int8_transfer import dequantize_int8_pallas, quantize_int8_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here: nothing to check
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # A compile for a described chip cannot be read back from the
        # persistent cache without one: keep the cache out of it.
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", cache_on)


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _hlo(f, *args) -> str:
    return jax.jit(f).lower(*args).compile().as_text()


@pytest.mark.parametrize("rows,d", [(16384, 2048), (20, 2048), (4096, 768)])
def test_int8_pair_compiles(one_chip, rows, d):
    q = _hlo(quantize_int8_pallas, _spec(one_chip, (rows, d)))
    dq = _hlo(lambda a, s: dequantize_int8_pallas(a, s),
              _spec(one_chip, (rows, d), jnp.int8),
              _spec(one_chip, (rows, d // 128), jnp.float32))
    assert "tpu_custom_call" in q and "tpu_custom_call" in dq


def test_flash_attention_compiles(one_chip):
    x = _spec(one_chip, (1, 4096, 8, 128))
    assert "tpu_custom_call" in _hlo(
        lambda q, k, v: flash_attention_pallas(q, k, v), x, x, x)


def test_decode_attention_compiles_mistral_nemo(one_chip):
    """Hq 32, Hkv 8, hd 128 over an 8192-token cache."""
    cache = _spec(one_chip, (1, 8192, 8, 128))
    assert "tpu_custom_call" in _hlo(
        lambda q, k, v, n: decode_attention_pallas(q, k, v, n),
        _spec(one_chip, (1, 32, 128)), cache, cache,
        _spec(one_chip, (), jnp.int32))


@pytest.mark.parametrize("h,n,g,chunk", [pytest.param(64, 128, 1, 256, id="64-128"),
                                         pytest.param(128, 16, 1, 256, id="128-16"),
                                         pytest.param(64, 128, 8, 128, id="64-128-g8")])
def test_ssd_scan_compiles_mamba2(one_chip, h, n, g, chunk):
    """The SSD pair, forward and backward, over a 2048-token sequence of
    batch 8, P 64: at mamba2's H 64, N 128 and at jamba's H·P 8192, N 16
    (chunk 256), and at Nemotron-H's H 64, N 128 in 8 B/C groups (chunk
    128)."""
    f32 = lambda shape: _spec(one_chip, shape, jnp.float32)
    args = (_spec(one_chip, (8, 2048, h, 64)), f32((8, 2048, h)), f32((8, 2048, h)),
            _spec(one_chip, (8, 2048, g, n)), _spec(one_chip, (8, 2048, g, n)), f32((h,)))

    def loss(*a):
        y, state = ssd_scan_pallas(*a, chunk=chunk)
        return jnp.sum(y) + jnp.sum(state)

    assert "tpu_custom_call" in _hlo(lambda *a: ssd_scan_pallas(*a, chunk=chunk), *args)
    assert _hlo(jax.grad(loss, argnums=range(6)), *args).count("tpu_custom_call") == 2


def test_mamba2_step_keeps_the_decay_tiles_in_vmem(one_chip, monkeypatch):
    """The split step at mamba2-1.3b's widths (four layers, split 3/4),
    traced as on a TPU, runs the SSD pair in the extract and in the tune
    pass, and no buffer has the (Q, Q, H) = (256, 256, 64) shape of the
    XLA scan's decay tiles and their autodiff residuals."""
    from repro.config import HapiConfig, RunConfig, ShapeConfig, TrainConfig
    from repro.configs import get_config
    from repro.core.tier_split import plan_tiers
    from repro.kernels import ops
    from repro.models.api import build_model
    from repro.train.steps import build_hapi_train_step, init_train_state

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    cfg = dataclasses.replace(get_config("mamba2-1.3b"), n_layers=4, vocab_size=512)
    shape = ShapeConfig("t", "train", 512, 2)
    hapi = HapiConfig(compress_transfer=True, cos_batch_min=1)
    rc = RunConfig(model=cfg, shape=shape, hapi=hapi, train=TrainConfig())
    model = build_model(cfg)
    plan = plan_tiers(cfg, shape, hapi, local_batch=2)
    place = lambda tree: jax.tree.map(
        lambda x: _spec(one_chip, x.shape, x.dtype), tree)
    state = place(jax.eval_shape(
        lambda k: init_train_state(model, rc, plan, k), jax.random.PRNGKey(0)))
    tokens = _spec(one_chip, (2, 512), jnp.int32)
    hlo = _hlo(build_hapi_train_step(model, rc, plan), state,
               {"tokens": tokens, "labels": tokens})
    kernels = re.findall(r"%(\w+)[.\d]* = [^\n]*custom_call_target=\"tpu_custom_call\"", hlo)
    assert kernels.count("ssd_scan_pallas") >= 3     # extract, tune forward, backward
    assert "256,256,64]" not in hlo


def test_int8_train_step_holds_the_kernels(one_chip, monkeypatch):
    """The split step with the int8 boundary, traced as on a TPU, compiles
    with the Pallas kernels in it (a mamba2 config cut to 128 wide)."""
    from repro.config import HapiConfig, RunConfig, ShapeConfig, TrainConfig
    from repro.configs import get_smoke_config
    from repro.core.tier_split import plan_tiers
    from repro.kernels import ops
    from repro.models.api import build_model
    from repro.train.steps import build_hapi_train_step, init_train_state

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    cfg = dataclasses.replace(get_smoke_config("mamba2-1.3b"), d_model=128,
                              param_dtype="bfloat16", compute_dtype="bfloat16")
    shape = ShapeConfig("t", "train", 64, 4)
    hapi = HapiConfig(compress_transfer=True, cos_batch_min=1)
    rc = RunConfig(model=cfg, shape=shape, hapi=hapi, train=TrainConfig())
    model = build_model(cfg)
    plan = plan_tiers(cfg, shape, hapi, local_batch=4)
    place = lambda tree: jax.tree.map(
        lambda x: _spec(one_chip, x.shape, x.dtype), tree)
    state = place(jax.eval_shape(
        lambda k: init_train_state(model, rc, plan, k), jax.random.PRNGKey(0)))
    tokens = _spec(one_chip, (4, 64), jnp.int32)
    hlo = _hlo(build_hapi_train_step(model, rc, plan), state,
               {"tokens": tokens, "labels": tokens})
    assert hlo.count("tpu_custom_call") >= 2


@pytest.mark.parametrize("k,n", [(2688, 1856), (1856, 2688)])
def test_grouped_matmul_compiles_nemotron(one_chip, monkeypatch, k, n):
    """The expert layer's grouped matmul, forward and backward, at
    Nemotron 3 Nano's up (2688 -> 1856) and down (1856 -> 2688) widths
    for 8 held experts over the 24576-row buffer of a 2 x 2048 batch:
    the Pallas kernels (gmm forward, gmm and tgmm backward) and no XLA
    ragged dot, whose TPU rewrite drops the named scopes."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    args = (_spec(one_chip, (24576, k)), _spec(one_chip, (8, k, n)),
            _spec(one_chip, (8,), jnp.int32))
    loss = lambda x, w, g: jnp.sum(ops.grouped_matmul(x, w, g).astype(jnp.float32))
    hlo = _hlo(jax.grad(loss, argnums=(0, 1)), *args)
    kernels = re.findall(r"%(\w+)[.\d]* = [^\n]*custom_call_target=\"tpu_custom_call\"", hlo)
    assert kernels and all("gmm" in name for name in kernels)
    assert any("tgmm" in name for name in kernels)
    assert "ragged-dot" not in hlo


def test_expert_layer_gathers_rows_nemotron(one_chip, monkeypatch):
    """The held experts' layer at Nemotron 3 Nano's widths (2 x 2048
    tokens, d 2688, 8 of 128 experts held, top 6), forward and backward:
    the megablox kernels, and no scatter whose result has rows of width
    2688, since dispatch and combine are row gathers both ways. Scatters
    of scalars (the kernels' own, the top-k's, the sorted positions)
    stay."""
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models import layers as L

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    cfg = dataclasses.replace(get_config("nemotron3-nano-30b-a3b"), experts_held=8)
    params = jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype),
                          jax.eval_shape(lambda k: L.experts_init(k, cfg),
                                         jax.random.PRNGKey(0)))
    x = _spec(one_chip, (2, 2048, cfg.d_model))
    loss = lambda p, x: jnp.sum(jnp.square(L.routed_experts(p, x, cfg)))
    hlo = _hlo(jax.value_and_grad(loss, argnums=(0, 1)), params, x)
    assert "tpu_custom_call" in hlo
    scatters = [line.split(" scatter(")[0] for line in hlo.splitlines() if " scatter(" in line]
    assert scatters
    assert not [s for s in scatters if re.search(r"\[(\d+,)*2688\]", s)]
