"""One authoritative int8 compression ratio, kernel -> splitter -> server.

Regression suite for the quantized wire path bugfix: Algorithm 1's
predicted wire bytes, the cost model's, and the simulated server's
charged bytes must all be the single figure derived from the kernel's
quantization geometry (``repro.kernels.ops.compression_ratio``) — no
hand-copied 0.25 / 0.53 constants anywhere, and no double-discounting
when a live executor already shipped int8.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import HapiCluster, NetworkSpec, TenantSpec
from repro.config import HapiConfig
from repro.core.cost_model import (transferred_per_iteration,
                                   wire_bytes_per_iteration)
from repro.core.profiler import profile_layered
from repro.core.splitter import choose_split
from repro.cos.objectstore import synthetic_image_store
from repro.cos.server import HapiServer, PostRequest
from repro.kernels import ops, ref
from repro.kernels.int8_transfer import dequantize_int8_pallas
from repro.kernels.ops import INT8_WIRE_RATIO, WIRE_TILE, compression_ratio
from repro.models.vision import alexnet

TRUNK = 1e9 / 8          # 1 Gbps, the paper's testbed rate


@pytest.fixture(scope="module")
def prof():
    return profile_layered(alexnet(100))


# ---------------------------------------------------------------------------
# The constant itself
# ---------------------------------------------------------------------------
def test_compression_ratio_exact_values():
    """(itemsize_q + scale_bytes/tile) / itemsize_act, exactly — NOT the
    old hardcoded 0.25 ("int8 is a quarter of fp32, scales are free")
    nor the old 0.53 rule of thumb."""
    assert compression_ratio(jnp.bfloat16, 128) == (1 + 4 / 128) / 2
    assert compression_ratio(jnp.bfloat16, 128) == 0.515625
    assert compression_ratio(jnp.float32, 128) == (1 + 4 / 128) / 4
    assert compression_ratio(jnp.float32, 128) == 0.2578125
    assert INT8_WIRE_RATIO == compression_ratio(jnp.bfloat16, WIRE_TILE)
    # Smaller tiles pay more scale overhead.
    assert compression_ratio(jnp.bfloat16, 8) == (1 + 4 / 8) / 2
    with pytest.raises(ValueError):
        compression_ratio(jnp.bfloat16, 0)


def test_ratio_matches_measured_kernel_bytes():
    """The derived constant equals the measured nbytes of an actual
    quantized payload (full 128-lane tiles)."""
    x = jnp.zeros((64, 256), jnp.bfloat16)
    q, s = ref.quantize_int8(x)
    wire = q.size * q.dtype.itemsize + s.size * s.dtype.itemsize
    raw = x.size * x.dtype.itemsize
    assert wire == raw * INT8_WIRE_RATIO


# ---------------------------------------------------------------------------
# Splitter == cost model == server (the bugfix's core invariant)
# ---------------------------------------------------------------------------
def test_splitter_cost_model_server_charge_identical_wire_bytes(prof):
    """The bytes Algorithm 1 predicts for its chosen split are exactly
    the bytes the simulated server charges for the compressed response
    (and the canonical cost-model helper agrees)."""
    train_batch = 500
    hapi = HapiConfig(network_bandwidth=TRUNK, compress_transfer=True)
    d = choose_split(prof, hapi, train_batch)

    assert d.wire_bytes_per_iter == pytest.approx(
        wire_bytes_per_iteration(prof, d.split_index, train_batch,
                                 compressed=True))
    assert d.wire_bytes_per_iter == pytest.approx(
        transferred_per_iteration(prof, d.split_index, train_batch,
                                  compress=INT8_WIRE_RATIO))

    store = synthetic_image_store("ds", n_samples=train_batch,
                                  object_size=train_batch, n_classes=100)
    srv = HapiServer(store, n_accelerators=2)
    (oname,) = store.object_names("ds")
    srv.submit(PostRequest(1, 0, "alexnet", d.split_index, oname,
                           train_batch, prof, 0.0, compress=True))
    (resp,) = srv.drain()
    assert resp.act_bytes == pytest.approx(d.wire_bytes_per_iter)


def test_uncompressed_request_charges_raw_bytes(prof):
    """compress_transfer=False (the default) stays byte-identical to the
    historical path: raw profile bytes, no ratio anywhere."""
    train_batch = 500
    d = choose_split(prof, HapiConfig(network_bandwidth=TRUNK), train_batch)
    assert d.wire_bytes_per_iter == pytest.approx(
        prof.out_bytes[d.split_index] * train_batch)
    store = synthetic_image_store("ds", n_samples=train_batch,
                                  object_size=train_batch, n_classes=100)
    srv = HapiServer(store, n_accelerators=2)
    (oname,) = store.object_names("ds")
    srv.submit(PostRequest(1, 0, "alexnet", d.split_index, oname,
                           train_batch, prof, 0.0))
    (resp,) = srv.drain()
    assert resp.act_bytes == pytest.approx(d.wire_bytes_per_iter)


# ---------------------------------------------------------------------------
# Live executors: measured payloads, no double discount
# ---------------------------------------------------------------------------
def _one_object_server(prof, n):
    store = synthetic_image_store("ds", n_samples=n, object_size=n,
                                  n_classes=100)
    srv = HapiServer(store, n_accelerators=2)
    (oname,) = store.object_names("ds")
    return srv, oname


def test_live_int8_executor_not_double_discounted(prof):
    """An executor whose payload leaves are already int8(+scales) has
    produced the actual wire payload: its measured nbytes must be
    charged as-is — multiplying by the ratio again was the bug."""
    n = 50
    srv, oname = _one_object_server(prof, n)
    q = jnp.zeros((n, 256), jnp.int8)
    s = jnp.zeros((n, 2), jnp.float32)
    srv.register_executor("alexnet", lambda payload, split, b: (q, s))
    srv.submit(PostRequest(1, 0, "alexnet", 5, oname, n, prof, 0.0,
                           compress=True))
    (resp,) = srv.drain()
    assert resp.act_bytes == q.size * 1 + s.size * 4


def test_live_raw_executor_charged_with_ratio(prof):
    """An executor that returns raw bf16 activations under a compressed
    request is charged measured nbytes x the authoritative ratio."""
    n = 50
    srv, oname = _one_object_server(prof, n)
    acts = jnp.zeros((n, 256), jnp.bfloat16)
    srv.register_executor("alexnet", lambda payload, split, b: acts)
    srv.submit(PostRequest(1, 0, "alexnet", 5, oname, n, prof, 0.0,
                           compress=True))
    (resp,) = srv.drain()
    assert resp.act_bytes == pytest.approx(
        acts.size * acts.dtype.itemsize * INT8_WIRE_RATIO)


# ---------------------------------------------------------------------------
# Int8 dispatch: the platform picks the kernel; both paths agree
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ops_dequantize_dtype_dispatch(dtype):
    """The Pallas kernel (interpreted) and the reference dequantize to the
    requested dtype identically, and ops (off-TPU) runs the reference."""
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 256), jnp.float32) * 2
    q, s = ref.quantize_int8(x)
    a = dequantize_int8_pallas(q, s, dtype=dtype, interpret=True)
    b = ref.dequantize_int8(q, s, dtype=dtype)
    c = ops.dequantize_int8(q, s, dtype=dtype)
    for out in (a, b, c):
        assert out.dtype == jnp.dtype(dtype)
    np.testing.assert_array_equal(np.asarray(a, np.float32),
                                  np.asarray(b, np.float32))
    np.testing.assert_array_equal(np.asarray(c, np.float32),
                                  np.asarray(b, np.float32))


@pytest.mark.parametrize("tpu", [False, True])
def test_ops_int8_dispatch_follows_platform(tpu, monkeypatch):
    """On TPU the int8 pair traces the Pallas kernels (compiled, never
    interpreted); elsewhere the XLA reference."""
    monkeypatch.setattr(ops, "on_tpu", lambda: tpu)
    x = jnp.ones((4, 256), jnp.float32)
    # Fresh lambdas: make_jaxpr caches traces by function identity.
    qs = jax.make_jaxpr(lambda a: ops.quantize_int8(a))(x)
    dq = jax.make_jaxpr(lambda q, s: ops.dequantize_int8(q, s))(
        *ref.quantize_int8(x))
    for jaxpr in (qs, dq):
        assert ("pallas_call" in str(jaxpr)) == tpu
        assert "interpret=True" not in str(jaxpr)


def test_ops_int8_on_tpu_rejects_unaligned_width(monkeypatch):
    """A width the compiled kernel cannot lay out is a clear error on
    TPU, not a Mosaic failure and not a silent fallback to the reference."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    with pytest.raises(ValueError, match="multiples of 128"):
        ops.quantize_int8(jnp.ones((4, 96), jnp.float32))
    with pytest.raises(ValueError, match="multiples of 128"):
        ops.dequantize_int8(jnp.ones((4, 96), jnp.int8),
                            jnp.ones((4, 3), jnp.float32))


# ---------------------------------------------------------------------------
# End to end: compression buys back pushdown under contention
# ---------------------------------------------------------------------------
def _contended_splits(prof, *, compress, n_tenants=2, seed=0):
    c = (HapiCluster(seed=seed)
         .with_servers(2, n_accelerators=2, flops_per_accel=197e12)
         .with_dataset("ds", n_samples=2000, object_size=500, n_classes=100)
         .with_network(NetworkSpec(trunk_bandwidth=TRUNK)))
    hapi = HapiConfig(network_bandwidth=TRUNK, compress_transfer=compress)
    handles = [c.tenant(TenantSpec(model="alexnet", profile=prof,
                                   hapi=hapi, client_flops=197e12,
                                   resplit_every=1))
               for _ in range(n_tenants)]
    results = c.run_epochs([(h, "ds", 500) for h in handles])
    return [r.split for r in results]


def test_compressed_contended_epoch_picks_shallower_split(prof):
    """Same trunk, same tenants: quantized activations fit through the
    contended trunk at an earlier boundary, so the compressed tenants'
    re-decided splits stay at-or-shallower than the raw tenants' —
    which must actually have migrated deeper for the comparison to
    mean anything."""
    raw = _contended_splits(prof, compress=False)
    qnt = _contended_splits(prof, compress=True)
    init = choose_split(prof, HapiConfig(network_bandwidth=TRUNK),
                        500).split_index
    assert max(raw) > init                  # contention pushed raw deeper
    assert max(qnt) <= max(raw)             # compression backs off less
