"""Dry-run machinery that is testable without 256 fake devices."""
import jax
import pytest

from repro.config import SHAPES, cell_is_runnable
from repro.configs import ARCH_IDS, all_configs, get_config
from repro.launch.specs import decode_specs, input_specs, param_specs
from repro.models.api import build_model


def test_cell_skip_matrix():
    cfgs = all_configs()
    runnable = [(a, s) for a in ARCH_IDS for s in SHAPES
                if cell_is_runnable(cfgs[a], SHAPES[s])]
    skipped = [(a, s) for a in ARCH_IDS for s in SHAPES
               if not cell_is_runnable(cfgs[a], SHAPES[s])]
    assert len(runnable) + len(skipped) == 4 * len(ARCH_IDS) == 44
    assert len(skipped) == 8
    assert all(s == "long_500k" for _, s in skipped)
    assert ("mamba2-1.3b", "long_500k") in runnable
    assert ("jamba-v0.1-52b", "long_500k") in runnable


def test_exact_published_dims():
    """The full configs must match the assignment table exactly."""
    c = get_config("moonshot-v1-16b-a3b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab_size, c.n_experts, c.top_k) == (48, 2048, 16, 16, 1408, 163840, 64, 6)
    c = get_config("grok-1-314b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab_size, c.n_experts, c.top_k) == (64, 6144, 48, 8, 32768, 131072, 8, 2)
    c = get_config("mistral-nemo-12b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff, c.vocab_size) == \
        (40, 5120, 32, 8, 14336, 131072)
    c = get_config("gemma2-9b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff, c.vocab_size) == \
        (42, 3584, 16, 8, 14336, 256000)
    assert c.local_global_period == 2 and c.logit_softcap == 30.0
    c = get_config("qwen3-32b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff, c.vocab_size) == \
        (64, 5120, 64, 8, 25600, 151936)
    assert c.qk_norm
    c = get_config("qwen1.5-110b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff, c.vocab_size) == \
        (80, 8192, 64, 8, 49152, 152064)
    assert c.qkv_bias
    c = get_config("mamba2-1.3b")
    assert (c.n_layers, c.d_model, c.vocab_size, c.ssm_state) == (48, 2048, 50280, 128)
    c = get_config("llava-next-mistral-7b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff, c.vocab_size) == \
        (32, 4096, 32, 8, 14336, 32000)
    c = get_config("whisper-small")
    assert (c.n_enc_layers, c.d_model, c.n_heads, c.d_ff, c.vocab_size) == \
        (12, 768, 12, 3072, 51865)
    c = get_config("jamba-v0.1-52b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab_size, c.n_experts, c.top_k) == (32, 4096, 32, 8, 14336, 65536, 16, 2)
    assert c.attn_period == 8 and c.moe_every == 2


def test_input_specs_no_allocation():
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for sname, shape in SHAPES.items():
            if not cell_is_runnable(cfg, shape):
                continue
            spec = input_specs(cfg, shape)
            for v in spec.values():
                assert isinstance(v, jax.ShapeDtypeStruct)
                assert v.shape[0] == shape.global_batch


def test_decode_specs_cache_length():
    cfg = get_config("mamba2-1.3b")
    model = build_model(cfg)
    cache, token, pos = decode_specs(model, cfg, SHAPES["long_500k"])
    leaves = jax.tree.leaves(cache)
    assert all(l.shape[0] == cfg.n_blocks for l in leaves)
    assert token.shape == (1, 1)


def test_param_counts_roughly_match_names():
    sizes = {
        "grok-1-314b": 314e9, "qwen1.5-110b": 110e9, "jamba-v0.1-52b": 52e9,
        "qwen3-32b": 32e9, "mistral-nemo-12b": 12e9,
        "moonshot-v1-16b-a3b": 16e9, "mamba2-1.3b": 1.3e9,
        "llava-next-mistral-7b": 7e9, "gemma2-9b": 9e9,
    }
    for arch, n in sizes.items():
        got = get_config(arch).param_count()
        # moonshot's assignment table (48L x 64e x d_ff 1408) totals ~28B;
        # we implement the table, not the marketing name.
        hi = 1.9 if arch == "moonshot-v1-16b-a3b" else 1.75
        assert 0.6 < got / n < hi, (arch, got / n)
