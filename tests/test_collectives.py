"""Collective helpers: tier transfer bytes, compressed psum correctness."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.collectives import (
    compressed_psum,
    decompress_boundary,
    tier_transfer,
)


def test_tier_transfer_bytes():
    acts = jnp.ones((4, 16, 256), jnp.bfloat16)
    plain, wire_p = tier_transfer(acts)
    comp, wire_c = tier_transfer(acts, compress=True)
    assert wire_c < 0.6 * wire_p
    rec = decompress_boundary(comp)
    np.testing.assert_allclose(np.asarray(rec, np.float32),
                               np.asarray(acts, np.float32), atol=0.05)


def test_compressed_psum_single_device():
    mesh = jax.make_mesh((1,), ("pod",))
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 128))

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=jax.sharding.PartitionSpec(),
        out_specs=(jax.sharding.PartitionSpec(), jax.sharding.PartitionSpec()),
        check_vma=False,
    )
    def f(v):
        return compressed_psum(v, "pod")

    total, err = f(x)
    np.testing.assert_allclose(np.asarray(total), np.asarray(x), atol=0.05)
    # Error feedback: quantization residual is bounded by a quant step.
    step = np.abs(np.asarray(x)).max() / 127
    assert float(jnp.max(jnp.abs(err))) <= step + 1e-5


def test_error_feedback_reduces_bias():
    """Accumulated compressed sums with error feedback track the true sum
    better than without."""
    rng = np.random.default_rng(0)
    xs = [jnp.asarray(rng.normal(size=(256,)) * 0.01) for _ in range(50)]
    from repro.kernels import ref

    def quant_roundtrip(v):
        q, s = ref.quantize_int8(v.reshape(2, 128))
        return ref.dequantize_int8(q, s).reshape(-1).astype(jnp.float32)

    # without EF
    err_plain = sum(quant_roundtrip(x) for x in xs) - sum(xs)
    # with EF
    e = jnp.zeros((256,))
    acc = jnp.zeros((256,))
    for x in xs:
        carry = x + e
        qd = quant_roundtrip(carry)
        e = carry - qd
        acc = acc + qd
    err_ef = acc - sum(xs)
    assert float(jnp.abs(err_ef).max()) <= float(jnp.abs(err_plain).max()) + 1e-6
