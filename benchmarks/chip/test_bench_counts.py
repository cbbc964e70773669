"""FLOP and byte counts from shapes, against sums worked by hand, and the
boundary bytes against the program's own extract output shapes."""
import json
import math
import sys
import types

import jax
import pytest

from chipbench import cells, counts

BENCH = cells.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


def _cfg(name):
    path = {c["name"]: c["file"] for c in BENCH["configs"]}[name]
    with open(cells.ROOT / path) as f:
        return json.load(f)


def test_mamba2_block_flops_by_hand():
    c = _cfg("mamba2-1.3b")
    s, d, di, n, h, p, q = 2048, 2048, 4096, 128, 64, 64, 256
    in_proj = 2 * d * (2 * di + 2 * n + h) * s          # z, x, B, C, dt
    out_proj = 2 * di * d * s
    conv = 2 * 4 * (di + 2 * n) * s
    intra = (2 * n + 2 * h * p) * (q + 1) / 2 * s       # causal half of each chunk
    states = 2 * 2 * n * h * p * s                      # state update and read
    assert counts.block_flops(c, s) == in_proj + out_proj + conv + intra + states
    assert in_proj == 2 * 2048 * 8512 * 2048


def test_nemo_block_flops_by_hand():
    c = _cfg("mistral-nemo-12b-8l")
    s = 2048
    qkv = 2 * 5120 * (32 + 16) * 128 * s
    out = 2 * 32 * 128 * 5120 * s
    mlp = 6 * 5120 * 14336 * s
    attn = 2 * s * s * 32 * 128
    assert counts.block_flops(c, s) == qkv + out + mlp + attn


@pytest.mark.parametrize("cell", CELLS)
def test_finetune_flops_compose(cell):
    """Each cell's count is composed of its blocks as the docstring of
    ``counts`` says, at the cell's own length and split."""
    from chipbench.kinds import finetune

    c = cells.resolve(cell)
    s, split = c.traffic["seq_len"], finetune.run_config(c)[1].split
    arch = cells.reference_module(c.config)
    n_blocks = c.config["n_layers"] // arch.layers_per_block(c.config)
    fwd = counts.block_flops(c.config, s)
    in_proj = arch.block_flops(c.config, s)[2]
    r = counts.finetune_flops_per_sample(c.config, s, split)
    assert 0 < split < n_blocks
    assert r["prefix"] == split * fwd
    assert r["suffix"] == 3 * (n_blocks - split) * fwd - in_proj
    assert r["head"] == 3 * 2 * s * c.config["d_model"] * c.config["vocab_size"]
    assert r["total"] == r["prefix"] + r["suffix"] + r["head"]


def _stub(**names):
    mod = types.ModuleType("chipbench.reference.stub_period7")
    mod.__dict__.update(names)
    return mod


@pytest.fixture
def stub_period7(monkeypatch):
    """A reference module whose block spans 7 layers (a hybrid's period),
    put in ``chipbench.reference`` under its own name."""
    import chipbench.reference

    mod = _stub(layer=lambda lp, h, c, P: h,
                block_flops=lambda c, s: (100.0 * s, 10.0 * s, 30.0 * s),
                layers_per_block=lambda c: 7)
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    monkeypatch.setattr(chipbench.reference, "stub_period7", mod, raising=False)
    return mod


def test_multi_layer_block_is_counted_in_blocks(stub_period7):
    """28 layers of 7-layer blocks are 4 blocks: a split after 3 blocks
    leaves 1 trained block, not 28 - 3 = 25 layers."""
    c = {"reference": "stub_period7", "n_layers": 28, "d_model": 64, "vocab_size": 100}
    s = 16
    r = counts.finetune_flops_per_sample(c, s, 3)
    assert r["prefix"] == 3 * 110.0 * s
    assert r["suffix"] == 3 * 1 * 110.0 * s - 30.0 * s
    assert r["head"] == 3 * 2 * s * 64 * 100
    assert counts.block_flops(c, s) == 110.0 * s


def test_resolve_refuses_a_reference_without_counts(tmp_path, monkeypatch):
    """A configuration whose reference lacks ``block_flops`` fails when
    the cell is resolved, which ``run.py`` does before any set-up, and
    the message names what is missing."""
    mod = _stub(layer=lambda lp, h, c, P: h, layers_per_block=lambda c: 1)
    mod.__name__ = "chipbench.reference.stub_nocount"
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    bench = cells.load_benchmark()
    w = dict(bench["workloads"][0])
    config = dict(_cfg(w["config"]), reference="stub_nocount")
    (tmp_path / "cfg.json").write_text(json.dumps(config))
    bench = dict(bench, configs=[{"name": w["config"], "file": "cfg.json"}], workloads=[w])
    with pytest.raises(AttributeError, match="block_flops"):
        cells.resolve(w["name"], bench, root=tmp_path)


def test_int8_kernel_bytes_by_hand():
    e = 8 * 2048 * 2048
    assert counts.quantize_bytes(e) == e * 2 + e + e // 128 * 4
    assert counts.dequantize_bytes(e) == e + e // 128 * 4 + e * 2


@pytest.mark.parametrize("cell,expected", [("mamba2-ft-2k", 4_325_376),
                                           ("nemo8l-ft-2k", 10_813_440)])
def test_boundary_bytes_per_sample(cell, expected):
    """The program's extract output at the cell's size, from its shapes
    alone (int8 plus a float32 scale per 128 lanes)."""
    from chipbench.kinds import finetune
    from repro.core.tier_split import make_extract_fn
    from repro.models.api import build_model

    c = cells.resolve(cell)
    rc, plan = finetune.run_config(c)
    model = build_model(rc.model)
    t = c.traffic
    frozen = jax.eval_shape(lambda k: model.split_params(model.init(k), plan.split)[0],
                            jax.random.PRNGKey(0))
    batch = {k: jax.ShapeDtypeStruct((t["batch"], t["seq_len"]), "int32")
             for k in ("tokens", "labels")}
    out = jax.eval_shape(make_extract_fn(model, plan), frozen, batch)
    total = sum(math.prod(x.shape) * x.dtype.itemsize for x in jax.tree.leaves(out))
    assert total / t["batch"] == expected
    d = rc.model.d_model
    assert expected == t["seq_len"] * d * 2 * 0.515625


@pytest.mark.parametrize("cell,flops,boundary", [
    ("mamba2-ft-2k", 74_266_846_429_184, 4_325_376),
    ("nemo8l-ft-2k", 24_824_910_970_880, 10_813_440)])
def test_job_counts_per_step(cell, flops, boundary):
    """What ``mfu.train`` and ``boundary_bytes_per_sample.train`` read,
    through ``Job.counts()`` as a run calls it, at the cell's own size:
    the counts of each cell as they stood before the count moved into the
    reference modules."""
    from chipbench.kinds import finetune

    job = finetune.Job(cells.resolve(cell), 3_000_000_019)
    t = job.t
    frozen = jax.eval_shape(job._params)[0]
    batch = {k: jax.ShapeDtypeStruct((t["batch"], t["seq_len"]), "int32")
             for k in ("tokens", "labels")}
    job.boundary_bytes = job.boundary_bytes_per_sample(frozen, batch)
    got = job.counts()
    assert got["flops_per_step"] == flops
    assert got["boundary_bytes_per_sample"] == boundary
