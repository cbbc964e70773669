"""Each plain reference against the program at a small size on the CPU,
both in float32, so that only the order of rounding differs: prefix
activations, the suffix loss and its gradients, on the benchmark's own
seeded weights."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import cells, testing, weights
from chipbench.reference.lm import LMReference

BENCH = cells.load_benchmark()


def _setup(cell_name):
    cell = testing.small_cell(cell_name, seq_len=64, batch=2)
    config = dict(cell.config, param_dtype="float32", compute_dtype="float32")
    from repro.models.api import build_model

    cfg = cells.model_config(config)
    model = build_model(cfg)
    split = cfg.freeze_index
    full = weights.full_params_fn(model)(weights.seed_key(2 ** 33 + 5))
    frozen, trainable = weights.split_full(full, split, cfg.tie_embeddings)
    toks = weights.token_rows(11, 2, 64, cfg.vocab_size)
    ref = LMReference(cells.reference_module(config), config)
    return model, split, frozen, trainable, toks, ref


@pytest.fixture(scope="module", params=[w["name"] for w in BENCH["workloads"]])
def setup(request):
    with jax.default_matmul_precision("highest"):
        yield _setup(request.param)


def test_prefix_activations_match(setup):
    model, split, frozen, _, toks, ref = setup
    with jax.default_matmul_precision("highest"):
        prog = model.forward_prefix(frozen, {"tokens": jnp.asarray(toks)}, split)
        for r in range(toks.shape[0]):
            want = ref.prefix(frozen, jnp.asarray(toks[r]))
            np.testing.assert_allclose(np.asarray(prog[r]), np.asarray(want),
                                       rtol=2e-4, atol=2e-4 * float(jnp.abs(want).max()))


def test_suffix_loss_and_gradients_match(setup):
    model, split, frozen, trainable, toks, ref = setup
    with jax.default_matmul_precision("highest"):
        batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(toks)}
        acts = model.forward_prefix(frozen, batch, split)
        loss, grads = jax.value_and_grad(model.loss_suffix)(trainable, acts, batch, split)
        r_loss, r_grads = 0.0, None
        for r in range(toks.shape[0]):
            l, g = ref.loss_grad(trainable, acts[r], jnp.asarray(toks[r]))
            r_loss += float(l) / toks.shape[0]
            g = jax.tree.map(lambda x: x / toks.shape[0], g)
            r_grads = g if r_grads is None else jax.tree.map(jnp.add, r_grads, g)
    assert abs(float(loss) - r_loss) <= 1e-5 * abs(r_loss)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree.leaves(r_grads)):
        scale = max(float(jnp.abs(b).max()), 1e-12)
        assert float(jnp.abs(a - b).max()) <= 1e-3 * scale, path


def test_int8_roundtrip_matches_program_kernel_reference():
    from chipbench.reference.common import int8_roundtrip
    from repro.kernels import ref as kref

    x = jax.random.normal(jax.random.PRNGKey(0), (4, 256), jnp.float32) * 3
    q, s = kref.quantize_int8(x)
    np.testing.assert_allclose(np.asarray(int8_roundtrip(x)),
                               np.asarray(kref.dequantize_int8(q, s, jnp.float32)),
                               rtol=0, atol=1e-6)


def test_seed_key_takes_large_seeds():
    a = weights.seed_key(2 ** 31 + 7)
    b = weights.seed_key(2 ** 40 + 7)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError):
        weights.seed_key(-1)


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_configs_state_their_departures(config):
    """The program multiplies the embedding by sqrt(d_model) in every
    family, so every configuration states it, at its small size too."""
    cell = next(w["name"] for w in BENCH["workloads"] if w["config"] == config)
    c = testing.small_cell(cell).config
    assert c["departures"]["embed_times_sqrt_d"] is True
    assert dataclasses.is_dataclass(cells.model_config(c))
