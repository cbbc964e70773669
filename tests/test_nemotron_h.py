"""Nemotron-H in the program, at small sizes on the CPU: the expert layer
that holds a share of the routed experts and drops no token, the layer
pattern, the cost profile Alg. 1 and Eq. 4 read, and the split step and
the storage server's live executor on the hybrid model."""
import dataclasses
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.core.profiler import profile_lm, sublayer_flops
from repro.models import layers as L
from repro.models.api import build_model
from repro.models.module import tree_param_count
from repro.models.transformer import PATTERN_LAYERS, block_plan

ARCH = "nemotron3-nano-30b-a3b"
ROOT = pathlib.Path(__file__).resolve().parents[1]


def _layer(**kw):
    """A float32 expert layer at the smoke width, its params and tokens."""
    cfg = dataclasses.replace(get_smoke_config(ARCH), **kw)
    key = jax.random.PRNGKey(3)
    params = L.experts_init(key, cfg)
    params["e_score_correction_bias"] = 0.3 * jax.random.normal(
        jax.random.fold_in(key, 7), (cfg.n_experts,))
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 16, cfg.d_model))
    return cfg, params, x


def _plain_loop(params, x, cfg):
    """The layer written as a loop over the held experts with dense masks:
    sigmoid scores plus the bias choose the top k, the chosen scores,
    normalized and scaled, weight each expert's relu^2 MLP; plus the
    shared expert."""
    scores = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", x, params["router"],
                                       precision="highest"))
    _, ids = jax.lax.top_k(scores + params["e_score_correction_bias"], cfg.top_k)
    w = jnp.take_along_axis(scores, ids, axis=-1)
    w = w / w.sum(-1, keepdims=True) * cfg.routed_scaling
    mlp = lambda up, down: jnp.einsum(
        "bsf,fd->bsd", jnp.square(jax.nn.relu(jnp.einsum("bsd,df->bsf", x, up))), down)
    out = mlp(params["shared"]["w_up"], params["shared"]["w_down"])
    for e in range(params["w_up"].shape[1]):
        gate = jnp.where(ids == e, w, 0.0).sum(-1)[..., None]
        out = out + gate * mlp(params["w_up"][:, e], params["w_down"][:, e])
    return out


@pytest.mark.parametrize("shares", [1, 2, 4])
def test_expert_shares_sum_to_the_uncut_layer(shares):
    """Holding all 8 routed experts, or splitting them into disjoint
    shares of 8 / ``shares`` (each routed over all 8, computing only its
    own experts), the shares' routed outputs plus the shared expert,
    counted once, give the uncut layer."""
    cfg, params, x = _layer(experts_held=8, n_experts=8, top_k=3)
    full = L.expert_layer_apply(params, x, cfg)
    np.testing.assert_allclose(full, _plain_loop(params, x, cfg), rtol=1e-4, atol=1e-4)
    size = 8 // shares
    routed = sum(
        L.routed_experts(dict(params, w_up=params["w_up"][:, i:i + size],
                              w_down=params["w_down"][:, i:i + size]), x, cfg, first=i)
        for i in range(0, 8, size))
    total = routed + L.relu2_mlp(params["shared"], x)
    np.testing.assert_allclose(total, full, rtol=1e-4, atol=1e-4)


def test_dropless_when_every_token_picks_one_expert():
    """A selection bias that sends every token to held expert 0 fills
    its group with every token; none is dropped (a capacity-capped layer
    would keep 1.25 x its share), and the layer, holding 4 of 8 routed
    experts, matches the plain loop, gradients included."""
    cfg, params, x = _layer(experts_held=4, n_experts=8, top_k=2)
    params["e_score_correction_bias"] = params["e_score_correction_bias"].at[0].set(10.0)
    ids, _ = L.route(params, x.reshape(-1, cfg.d_model), cfg)
    assert bool((ids == 0).any(-1).all())
    got = L.expert_layer_apply(params, x, cfg)
    np.testing.assert_allclose(got, _plain_loop(params, x, cfg), rtol=1e-4, atol=1e-4)
    loss = lambda f: lambda p: jnp.sum(jnp.sin(f(p, x, cfg)))
    g, want = jax.grad(loss(L.expert_layer_apply))(params), jax.grad(loss(_plain_loop))(params)
    for name in ("router", "w_up", "w_down"):
        np.testing.assert_allclose(g[name], want[name], rtol=1e-3, atol=1e-4, err_msg=name)
    assert float(jnp.abs(g["e_score_correction_bias"]).max()) == 0.0


def test_undefined_rows_stay_out_of_the_layer(monkeypatch):
    """The grouped matmul leaves rows past every group undefined (on TPU,
    unwritten memory); filled with NaN here, they reach neither the
    layer's output nor any gradient."""
    from repro.kernels import ops

    gmm = ops.grouped_matmul

    def undefined_tail(x, w, sizes, **kw):
        rows = (jnp.arange(x.shape[0]) < sizes.sum())[:, None]
        return jnp.where(rows, gmm(x, w, sizes, **kw), jnp.nan)

    monkeypatch.setattr(ops, "grouped_matmul", undefined_tail)
    cfg, params, x = _layer(experts_held=4, n_experts=8, top_k=3)
    loss = lambda p, x: jnp.sum(L.expert_layer_apply(p, x, cfg))
    assert bool(jnp.isfinite(L.expert_layer_apply(params, x, cfg)).all())
    grads = jax.grad(loss, argnums=(0, 1))(params, x)
    assert all(bool(jnp.isfinite(g).all()) for g in jax.tree.leaves(grads))


@pytest.mark.parametrize("k,held,first", [(3, 4, 0), (4, 2, 0), (2, 3, 2)],
                         ids=["k-under-held", "k-over-held", "first-2"])
def test_gather_pair_matches_scatter_add(k, held, first):
    """Dispatch and combine of the held experts, row gathers each the
    other's transpose, against the plain formulation (a row gather in, a
    float32 ``.at[].add`` out), values and gradients for the tokens, the
    gate weights and the experts' output rows; the output rows past
    every group hold NaN and reach nothing."""
    t, d, n_experts = 16, 8, 8
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    _, ids = jax.lax.top_k(jax.random.uniform(ks[0], (t, n_experts)), k)
    m = t * min(k, held)
    order, pos, sizes = L.sort_pairs(ids, held, first)
    order, n = order[:m], sizes.sum()
    np.testing.assert_array_equal(pos.reshape(-1)[order], np.arange(m))
    assert 0 < int(n) < t * k
    rows = (jnp.arange(m) < n)[:, None]
    x = jax.random.normal(ks[1], (t, d))
    gate = jax.random.uniform(ks[2], (t, k))
    yb = jnp.where(rows, jax.random.normal(ks[3], (m, d)), jnp.nan)
    rx, ry = jax.random.normal(ks[4], (m, d)), jax.random.normal(ks[5], (t, d))

    def pair(x, gate, yb):
        return L.dispatch(x, order // k, pos, n), L.combine(yb, gate, order, pos, n)

    def plain(x, gate, yb):
        tok = order // k
        contrib = jnp.where(rows, yb, 0) * gate.reshape(-1)[order][:, None]
        return (jnp.where(rows, x[tok], 0),
                jnp.zeros((t, d), jnp.float32).at[tok].add(contrib))

    loss = lambda f: lambda *a: sum(jnp.sum(jnp.sin(o) * r) for o, r in zip(f(*a), (rx, ry)))
    for got, want in zip(pair(x, gate, yb), plain(x, gate, yb)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    got = jax.grad(loss(pair), argnums=(0, 1, 2))(x, gate, yb)
    want = jax.grad(loss(plain), argnums=(0, 1, 2))(x, gate, yb)
    for name, g, w in zip(("x", "gate", "yb"), got, want):
        assert g.dtype == w.dtype, name
        assert bool(jnp.isfinite(g).all()), name
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6, err_msg=name)


def test_grouped_matmul_kernel_matches_ragged_dot():
    """The Pallas grouped matmul (interpreted) against XLA's ragged_dot,
    forward and backward, over groups of uneven, empty and partial-tile
    sizes; rows past every group are left out, as the layer masks them."""
    from repro.kernels import ops

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(ks[0], (256, 128))
    w = jax.random.normal(ks[1], (4, 128, 192))
    sizes = jnp.array([50, 0, 70, 36], jnp.int32)
    rows = (jnp.arange(256) < sizes.sum())[:, None]

    def f(interpret):
        return lambda x, w: jnp.where(rows, ops.grouped_matmul(x, w, sizes,
                                                              interpret=interpret), 0)

    np.testing.assert_allclose(f(True)(x, w), f(False)(x, w), rtol=1e-5, atol=1e-3)
    grads = [jax.grad(lambda a, b: jnp.sum(f(i)(a, b) ** 2), argnums=(0, 1))(x, w)
             for i in (True, False)]
    for got, want in zip(*grads):
        got, want = jnp.where(rows, got, 0) if got.ndim == 2 else got, \
            jnp.where(rows, want, 0) if want.ndim == 2 else want
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3 * float(jnp.abs(want).max()))


def test_block_plan_of_the_pattern():
    """One single-branch layer per letter; the cut configuration repeats
    MEMEM*E, the published one is one block of its 52 layers, 23 M, 23 E
    and 6 *."""
    m, e, a = PATTERN_LAYERS["M"], PATTERN_LAYERS["E"], PATTERN_LAYERS["*"]
    assert (m.mixer, m.ffn, e.mixer, e.ffn, a.mixer, a.ffn) == (
        "mamba", "none", "none", "experts", "attn", "none")
    cfg = get_smoke_config(ARCH)
    assert block_plan(cfg) == [m, e, m, e, m, a, e]
    assert (cfg.n_blocks, cfg.layers_per_block) == (2, 7)
    full = get_config(ARCH)
    plan = block_plan(full)
    assert (full.n_blocks, len(plan)) == (1, 52)
    assert (plan.count(m), plan.count(e), plan.count(a)) == (23, 23, 6)
    params = build_model(cfg).init(jax.random.PRNGKey(0))["blocks"]
    assert sorted(params["sub0"]) == ["ln_mixer", "mamba"]
    assert sorted(params["sub1"]) == ["experts", "ln_ffn"]
    assert sorted(params["sub5"]) == ["attn", "ln_mixer"]


@pytest.mark.parametrize("which", ["smoke", "published", "cut"])
def test_cost_profile_counts_the_init_tree(which):
    """``param_count`` and ``block_params``, which Alg. 1 and Eq. 4 price
    the split with, equal the sizes of ``model.init``'s tree: grouped
    B/C, explicit SSM heads, the held relu^2 experts (two matrices each)
    and the shared expert; and the profile's prefix bytes follow them."""
    cfg = get_smoke_config(ARCH) if which == "smoke" else get_config(ARCH)
    if which == "cut":
        cfg = dataclasses.replace(cfg, n_layers=28, layer_pattern="MEMEM*E",
                                  experts_held=8, vocab_size=16384)
    tree = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    assert cfg.param_count() == tree_param_count(tree)
    assert cfg.n_blocks * cfg.block_params() == tree_param_count(tree["blocks"])
    prof = profile_lm(cfg, 64)
    itemsize = 2 if cfg.param_dtype == "bfloat16" else 4
    assert prof.prefix_param_bytes[1] == (cfg.padded_vocab * cfg.d_model
                                          + cfg.block_params()) * itemsize


def test_expert_layer_flops_under_balanced_routing():
    """The profiler prices an E layer as its router, top_k x E_held /
    E_routed relu^2 experts a token (up and down, no gate) and the shared
    expert."""
    cfg = dataclasses.replace(get_config(ARCH), experts_held=8)
    d, s = cfg.d_model, 2048
    want = 2 * s * d * 128 + 2 * s * 6 * 8 / 128 * 2 * d * 1856 + 2 * s * 2 * d * 3712
    assert sublayer_flops(cfg, PATTERN_LAYERS["E"], s) == want


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_split_step_trains_the_hybrid():
    """build_lm -> plan_tiers -> build_hapi_train_step with the int8
    boundary, at the smoke config: finite losses, first loss checked
    against the unsplit model by the smoke script itself."""
    out = _chip_smoke().train_phase(ARCH, smoke=True, seq=32, batch=4, steps=3, compress=True)
    assert len(out["losses"]) == 3 and all(np.isfinite(out["losses"]))


def test_cos_server_serves_the_hybrid_prefix():
    """A HapiServer with the live executor (``make_extract_executor``)
    runs the frozen hybrid prefix for every request of an object; the
    int8 activations it returns match a direct forward of the prefix to
    within one quantization step. The server prices the requests with the
    smoke configuration's own profile (the published model's prefix,
    63 GB, fits no accelerator)."""
    from repro.config import HapiConfig, ShapeConfig
    from repro.core.tier_split import make_extract_executor, plan_tiers
    from repro.cos.objectstore import ObjectStore
    from repro.cos.server import HapiServer, PostRequest
    from repro.kernels import ops
    from repro.train.steps import init_split_params

    cfg = get_smoke_config(ARCH)
    model = build_model(cfg)
    shape = ShapeConfig("serve", "train", 32, 4)
    hapi = HapiConfig(compress_transfer=True, cos_batch_min=1)
    plan = plan_tiers(cfg, shape, hapi, local_batch=4)
    frozen, _ = init_split_params(model, plan.split, jax.random.PRNGKey(0))
    tokens = np.random.default_rng(1).integers(0, cfg.vocab_size, (8, 32), dtype=np.int32)
    store = ObjectStore()
    store.put_dataset("hybrid", {"tokens": tokens}, object_size=4)
    server = HapiServer(store, n_accelerators=1)
    server.register_executor(ARCH, make_extract_executor(model, frozen, plan))
    prof = profile_lm(cfg, 32)
    for i, name in enumerate(sorted(store.objects)):
        server.submit(PostRequest(i, 0, ARCH, plan.split, name, 4, prof, 0.0, compress=True))
    responses = server.drain()
    assert sorted(r.req_id for r in responses) == [0, 1]
    for r in responses:
        q, scales = r.acts
        direct = model.forward_prefix(frozen, store.objects[r.object_name].payload, plan.split)
        deq = ops.dequantize_int8(q, scales, dtype=jnp.float32)
        step = np.repeat(np.asarray(scales), cfg.d_model // scales.shape[-1], axis=-1)
        assert float((np.abs(np.asarray(deq) - np.asarray(direct, np.float32)) / step).max()) <= 1.01
