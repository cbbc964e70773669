"""The Nemotron-H cell (``nano28l-ft-2k``): its counts, pinned at the
cell's own size, and the program's faults that ``correct`` has to
catch, each planted in the program at the small size on the CPU and
judged against the small cell's limits."""
import dataclasses
import importlib.util

import jax
import jax.numpy as jnp
import pytest

from chipbench import cells, counts, testing
from chipbench.kinds import finetune

CELL = "nano28l-ft-2k"
SEED = 3_000_000_019          # above 2**31, as benchmark seeds may be


def test_job_counts_per_step():
    """What ``mfu.train`` and ``boundary_bytes_per_sample.train`` read,
    through ``Job.counts()`` as a run calls it: 2.90 GFLOP a token over
    2 x 2048 tokens, and int8 plus a float32 scale per 128 of 2688 lanes
    a token at the boundary."""
    job = finetune.Job(cells.resolve(CELL), SEED)
    t = job.t
    frozen = jax.eval_shape(job._params)[0]
    batch = {k: jax.ShapeDtypeStruct((t["batch"], t["seq_len"]), "int32")
             for k in ("tokens", "labels")}
    job.boundary_bytes = job.boundary_bytes_per_sample(frozen, batch)
    got = job.counts()
    assert (job.plan.split, job.plan.cos_batch, job.cfg.n_blocks) == (3, 2, 4)
    assert got["flops_per_step"] == 23_754_675_585_024 / 2
    assert got["boundary_bytes_per_sample"] == 5_677_056 == 2048 * (2688 + 2688 // 128 * 4)


def test_block_flops_by_hand():
    """One period MEMEM*E at seq 2048: three Mamba-2 layers with 8 B/C
    groups, one GQA layer without positions, three expert layers counted
    as 6 x 8 / 128 routed relu^2 experts, the router and the shared
    expert."""
    c, s = cells.resolve(CELL).config, 2048
    d, di, gn, h, q = 2688, 4096, 8 * 128, 64, 128
    in_proj = 2 * d * (2 * di + 2 * gn + h) * s
    mamba = (in_proj + 2 * di * d * s, 2 * 4 * (di + 2 * gn) * s
             + (2 * gn + 2 * h * 64) * (q + 1) / 2 * s + 2 * 2 * 128 * h * 64 * s)
    attn = (2 * d * 36 * 128 * s + 2 * 32 * 128 * d * s, 2 * s * s * 32 * 128)
    experts = 2 * d * 128 * s + 6 * 8 / 128 * 4 * d * 1856 * s + 4 * d * 3712 * s
    proj, mix, first = cells.reference_module(c).block_flops(c, s)
    assert proj == 3 * mamba[0] + attn[0] + 3 * experts
    assert mix == 3 * mamba[1] + attn[1]
    assert first == in_proj
    assert counts.block_flops(c, s) == proj + mix


def _run_module():
    spec = importlib.util.spec_from_file_location("chipbench_run", cells.BENCH_DIR / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _with_config(**changes):
    """The program built from a configuration changed as given; the
    reference reads the cell's own."""
    build = cells.model_config
    return lambda config: dataclasses.replace(build(config), **changes)


def _route_with(**changes):
    from repro.models import layers

    route = layers.route
    return lambda params, x, cfg: route(params, x, dataclasses.replace(cfg, **changes))


def _softmax_route():
    def softmax(params, x, cfg):
        logits = jnp.dot(x, params["router"], precision=jax.lax.Precision.HIGHEST)
        scores = jax.nn.softmax(logits, axis=-1)
        _, ids = jax.lax.top_k(scores, cfg.top_k)
        w = jnp.take_along_axis(scores, ids, axis=-1)
        return ids, w / (w.sum(-1, keepdims=True) + 1e-20) * cfg.routed_scaling
    return softmax


def _route_without_bias():
    from repro.models import layers

    route = layers.route

    def unbiased(params, x, cfg):
        bias = params["e_score_correction_bias"]
        return route(dict(params, e_score_correction_bias=jnp.zeros_like(bias)), x, cfg)
    return unbiased


def _bc_of_group_zero():
    from repro.models import ssm

    groups = ssm.bc_groups
    return lambda bc, cfg: jnp.repeat(groups(bc, cfg)[..., :1, :], cfg.ssm_groups, axis=-2)


def _fault(name):
    from repro.models import layers, ssm

    return {
        "routed_scaling_1": (layers, "route", lambda: _route_with(routed_scaling=1.0)),
        "softmax_router": (layers, "route", _softmax_route),
        "bias_not_in_selection": (layers, "route", _route_without_bias),
        "bc_shared_by_all_heads": (ssm, "bc_groups", _bc_of_group_zero),
        "norm_per_head": (cells, "model_config",
                          lambda: _with_config(ssm_norm_group=testing.small_config(
                              "nemotron3-nano-28l")["ssm_headdim"])),
        "shared_expert_dropped": (layers, "relu2_mlp", lambda: lambda p, x: jnp.zeros_like(x)),
    }[name]


@pytest.mark.parametrize("fault", ["routed_scaling_1", "softmax_router", "bias_not_in_selection",
                                   "bc_shared_by_all_heads", "norm_per_head",
                                   "shared_expert_dropped"])
def test_planted_fault_is_not_correct(fault, monkeypatch, capsys):
    """Each fault of the Nemotron-H block, planted in the program alone,
    fails ``correct`` against the small cell's limits."""
    module, name, make = _fault(fault)
    monkeypatch.setattr(module, name, make())
    result = _run_module().run_cell(testing.small_cell(CELL), SEED, 0.2, False,
                                    jax.devices()[0], 1, None)
    capsys.readouterr()
    assert not result["correct"], result["checks"]


def test_expert_layer_readers(tmp_path, monkeypatch):
    """``moe_ms.train`` reads the self time of the ops under the expert
    layer's scopes inside the phases, forward and backward;
    ``experts_roofline.train`` reads its cell from the command line and is
    an error, not a silent metric, where the command line names none."""
    import sys

    from chipbench import xplane
    from test_bench_scopes import _synthetic, _write_trace

    names = ["jit(train_step)/hapi.extract/while/body/moe.route/dot_general:",
             "jit(train_step)/hapi.extract/while:",
             "jit(train_step)/hapi.extract/hapi.quantize/pallas_call:",
             "jit(train_step)/hapi.tune/hapi.dequantize/pallas_call:",
             "jit(train_step)/hapi.tune/transpose(jvp(moe.experts))/dot_general:",
             "jit(train_step)/hapi.tune/moe.shared/copy:",
             "jit(train_step)/hapi.adamw/sub:",
             None]
    path = tmp_path / "t" / "host.xplane.pb"
    _write_trace(path, _synthetic().ops, names)
    tr = xplane.load(str(path))
    lo, hi = xplane.window(tr)
    ctx = dict(trace=tr, trace_path=str(path), lo=lo, hi=hi, steps=2,
               peaks={"bf16_flops_per_s": 197e12})
    assert cells.metric_reader("moe_ms.train")(ctx) == pytest.approx((100 + 70 + 20) * 1e-6 / 2)
    assert cells.metric_reader("tune_ms.train")(ctx) == pytest.approx((70 + 20) * 1e-6 / 2)
    roofline = cells.metric_reader("experts_roofline.train")
    monkeypatch.setattr(sys, "argv", ["run.py", "--seed", "1"])
    with pytest.raises(RuntimeError, match="--workload"):
        roofline(ctx)
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", CELL, "--seed", "1"])
    spec = importlib.util.spec_from_file_location(
        "experts_roofline", cells.BENCH_DIR / "metrics" / "experts_roofline.train.py")
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    flops = reader.expert_flops_per_step(cells.resolve(CELL))
    assert roofline(ctx) == pytest.approx(100 * flops / (70e-9 / 2 * 197e12))
