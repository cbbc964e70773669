"""FLOP and byte counts from shapes, against sums worked by hand, and the
boundary bytes against the program's own extract output shapes."""
import json
import math

import jax
import pytest

from chipbench import cells, counts

CFG = cells.BENCH_DIR / "configs"


def _cfg(name):
    with open(CFG / f"{name}.json") as f:
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


@pytest.mark.parametrize("name,split", [("mamba2-1.3b", 36), ("mistral-nemo-12b-8l", 7)])
def test_finetune_flops_compose(name, split):
    c = _cfg(name)
    s = 2048
    fwd = counts.block_flops(c, s)
    in_proj = counts.BLOCKS[c["family"]](c, s)[2]
    r = counts.finetune_flops_per_sample(c, s, split)
    assert r["prefix"] == split * fwd
    assert r["suffix"] == 3 * (c["n_layers"] - split) * fwd - in_proj
    assert r["head"] == 3 * 2 * s * c["d_model"] * c["vocab_size"]
    assert r["total"] == r["prefix"] + r["suffix"] + r["head"]


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
