"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
except ImportError:                       # pragma: no cover - env dependent
    import _propcheck as st
    from _propcheck import given, settings

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.int8_transfer import dequantize_int8_pallas, quantize_int8_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas

KEY = jax.random.PRNGKey(0)


def _qkv(b, s, h, hd, dtype):
    ks = jax.random.split(KEY, 3)
    mk = lambda k: jax.random.normal(k, (b, s, h, hd), jnp.float32).astype(dtype)
    return mk(ks[0]), mk(ks[1]), mk(ks[2])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,h,hd,causal,window,cap",
    [
        (2, 256, 4, 64, True, None, None),
        (1, 512, 2, 128, True, None, None),
        (2, 384, 2, 64, True, 128, None),   # sliding window + seq padding
        (1, 256, 2, 64, False, None, None), # bidirectional (whisper encoder)
        (2, 256, 4, 64, True, None, 50.0),  # gemma softcap
        (1, 128, 1, 32, True, None, None),  # minimal
    ],
)
def test_flash_attention(b, s, h, hd, causal, window, cap, dtype):
    q, k, v = _qkv(b, s, h, hd, dtype)
    out = flash_attention_pallas(
        q, k, v, causal=causal, window=window, softcap=cap,
        q_block=128, kv_block=128, interpret=True,
    )
    exp = ref.flash_attention(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        causal=causal, window=window, softcap=cap,
    )
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(exp, np.float32), atol=tol, rtol=tol
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,hq,hkv,hd,length",
    [
        (2, 1024, 8, 2, 64, 700),
        (1, 512, 4, 4, 128, 512),
        (2, 768, 16, 8, 64, 100),   # GQA 2:1, short fill
        (1, 300, 8, 8, 64, 300),    # padding path (300 % 256 != 0)
    ],
)
def test_decode_attention(b, s, hq, hkv, hd, length, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, hq, hd), jnp.float32).astype(dtype)
    kc = jax.random.normal(ks[1], (b, s, hkv, hd), jnp.float32).astype(dtype)
    vc = jax.random.normal(ks[2], (b, s, hkv, hd), jnp.float32).astype(dtype)
    out = decode_attention_pallas(q, kc, vc, length, s_block=256, interpret=True)
    exp = ref.decode_attention(
        q.astype(jnp.float32), kc.astype(jnp.float32), vc.astype(jnp.float32),
        jnp.int32(length),
    )
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(exp, np.float32), atol=tol, rtol=tol
    )


def _ssd_inputs(b, s, h, p, n, strong=False, dtype=jnp.float32, g=1):
    """SSD inputs as the mixer makes them, (x, dtA, dt, B, C, D), with B
    and C in ``g`` groups; ``strong`` gives decays whose exponents above
    the diagonal overflow f32 unless masked."""
    ks = jax.random.split(KEY, 6)
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)) + (3.0 if strong else 0.0))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3 + (1.5 if strong else 0.0))
    B_ = (jax.random.normal(ks[3], (b, s, g, n)) * 0.3).astype(dtype)
    C_ = (jax.random.normal(ks[4], (b, s, g, n)) * 0.3).astype(dtype)
    D = jax.random.normal(ks[5], (h,))
    return x, dt * A, dt, B_, C_, D


def _with_skip(scan, args):
    """An SSD scan without the skip, ``scan(x, dtA, dt, B, C)``, plus
    D x, cast to x's dtype as the mixer casts it."""
    x, D = args[0], args[5]
    y, state = scan(*args[:5])
    return (y + D[None, None, :, None] * x.astype(jnp.float32)).astype(x.dtype), state


def _ssd_quadratic(x, dtA, dt, B_, C_, D):
    """The SSD in its quadratic (dual) form, per group, plus the skip:
    y_t = sum_{s <= t} exp(cum_t - cum_s) (C_t . B_s) dt_s x_s + D x_t, where
    head h reads group h // (H / G); float32, no chunks, cast to x's dtype
    as the mixer casts it."""
    b, s, h, p = x.shape
    g = B_.shape[2]
    x32 = x.astype(jnp.float32)
    cum = jnp.cumsum(dtA.astype(jnp.float32), axis=1)                   # (B, S, H)
    tri = jnp.tril(jnp.ones((s, s), bool))[None, :, :, None]
    seg = jnp.exp(jnp.where(tri, cum[:, :, None, :] - cum[:, None, :, :], -jnp.inf))
    cb = jnp.einsum("btgn,bsgn->btsg", C_.astype(jnp.float32), B_.astype(jnp.float32),
                    precision="highest")
    w = seg * jnp.repeat(cb, h // g, axis=-1)                           # (B, T, S, H)
    y = jnp.einsum("btsh,bshp->bthp", w, x32 * dt[..., None], precision="highest")
    return (y + D[None, None, :, None] * x32).astype(x.dtype)


def _cases(rows, groups=(1, 2)):
    """Each row at every group count that divides its heads; one group
    keeps the row's plain id."""
    out = []
    for row in rows:
        for g in groups:
            if row[2] % g == 0:
                rid = "-".join(str(v) for v in row)
                out.append(pytest.param(*row, g, id=rid if g == 1 else f"{rid}-g{g}"))
    return out


@pytest.mark.parametrize(
    "b,s,h,p,n,chunk,g",
    _cases([
        (2, 512, 8, 64, 128, 128),
        (1, 256, 4, 32, 64, 64),
        (1, 256, 4, 32, 16, 128),   # jamba-like small state
        (2, 128, 8, 64, 128, 128),  # single chunk
    ]),
)
def test_ssd_scan(b, s, h, p, n, chunk, g):
    """The kernel against the sequential oracle and the XLA twin, and
    both against the quadratic form, with B and C in g groups."""
    args = _ssd_inputs(b, s, h, p, n, g=g)
    y, st = ssd_scan_pallas(*args, chunk=chunk, interpret=True)
    ye, ste = _with_skip(ref.ssd_reference, args)
    yx, stx = _with_skip(lambda *a: ref.ssd_chunked(*a, None, chunk=chunk), args)
    yq = _ssd_quadratic(*args)
    for got, want in ((y, ye), (st, ste), (yx, ye), (stx, ste), (y, yq), (yx, yq)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-3, rtol=2e-3)


def test_ssd_kernel_matches_chunked_model_path():
    """The model's XLA SSD (ref.ssd_chunked) and the Pallas kernel agree,
    with a grid chunk of 256 swept in two sub-chunks."""
    args = _ssd_inputs(1, 512, 4, 32, 64)
    y1, s1 = ssd_scan_pallas(*args, chunk=256, interpret=True)
    y2, s2 = _with_skip(lambda *a: ref.ssd_chunked(*a, None, chunk=256), args)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize(
    "b,s,h,p,n,chunk,strong,g",
    _cases([
        (2, 256, 4, 32, 16, 64, False),     # four chunks, one head block
        (1, 256, 4, 64, 32, 128, True),     # strong decay (masked exponent)
        (1, 512, 16, 64, 16, 256, False),   # two head blocks, two sub-chunks a chunk
        (2, 512, 8, 128, 16, 256, True),    # P of 128 lanes: one head a group
        (1, 128, 2, 32, 16, 128, False),    # one chunk, H x P under 128 lanes
    ]),
)
def test_ssd_scan_grad(b, s, h, p, n, chunk, strong, g):
    """The custom VJP's backward kernel against autodiff of the XLA scan,
    with cotangents on y and on the final state, for bf16 x, B and C as
    the mixer feeds them, with B and C in g groups; the forward also
    against the sequential oracle, and both scans' VJP of y against the
    quadratic form's."""
    args = _ssd_inputs(b, s, h, p, n, strong, jnp.bfloat16, g)
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    wy = jax.random.normal(ks[0], (b, s, h, p))
    ws = jax.random.normal(ks[1], (b, h, n, p))

    def loss(scan, with_state=True):
        def f(*a):
            y, st = scan(*a)
            return jnp.sum(y * wy) + (jnp.sum(st * ws) if with_state else 0.0)
        return f

    kernel = lambda *a: ssd_scan_pallas(*a, chunk=chunk, interpret=True)
    xla = lambda *a: _with_skip(lambda *u: ref.ssd_chunked(*u, None, chunk), a)
    quadratic = lambda *a: (_ssd_quadratic(*a), None)
    ye, _ = _with_skip(ref.ssd_reference, args)
    np.testing.assert_allclose(np.asarray(kernel(*args)[0], np.float32),
                               np.asarray(ye, np.float32), atol=3e-2, rtol=1e-2)
    grad = lambda f: jax.grad(f, argnums=range(6))(*args)
    got, exp = grad(loss(kernel)), grad(loss(xla))
    pairs = [(got, exp)]
    if s <= 256:
        q = grad(loss(quadratic, with_state=False))
        pairs += [(grad(loss(kernel, False)), q), (grad(loss(xla, False)), q)]
    for got, exp in pairs:
        for name, gr, e in zip(("x", "dtA", "dt", "B", "C", "D"), got, exp):
            gr, e = np.asarray(gr, np.float32), np.asarray(e, np.float32)
            assert np.isfinite(gr).all(), name
            # x, B and C take bf16 cotangents: within a bf16 rounding of the largest
            tol = 1e-2 if name in ("x", "B", "C") else 1e-4
            np.testing.assert_allclose(gr, e, atol=tol * np.abs(e).max(), err_msg=name)


@pytest.mark.parametrize("s,chunk,p,init,kernel", [
    (512, 256, 64, False, True),     # tiles: the compiled kernel
    (512, 64, 64, False, False),     # a 64-step chunk is under one lane tile
    (512, 256, 48, False, False),    # 48-lane heads do not divide 128 lanes
    (512, 256, 64, True, False),     # an initial state
])
def test_ssd_scan_dispatch_by_shape(monkeypatch, s, chunk, p, init, kernel):
    """On TPU ``ops.ssd_scan`` runs the kernel where its blocks tile the
    input and no initial state is given, and the XLA scan otherwise, which
    agrees with the sequential oracle."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    args = _ssd_inputs(1, s, 4, p, 16)
    state = jnp.ones((1, 4, 16, p), jnp.float32) * 0.1 if init else None
    scan = lambda *a: ops.ssd_scan(*a, state, chunk=chunk)
    assert ("pallas_call" in str(jax.make_jaxpr(scan)(*args))) == kernel
    if not kernel:
        y, st = scan(*args)
        ye, ste = _with_skip(lambda *a: ref.ssd_reference(*a, state), args)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ye), atol=2e-3, rtol=2e-3)
        np.testing.assert_allclose(np.asarray(st), np.asarray(ste), atol=2e-3, rtol=2e-3)


def test_ssd_plan_needs_whole_chunks():
    """A sequence that is not a whole number of chunks has no plan; at
    mamba2's widths a chunk of 256 runs as two sub-chunks of 128 over
    blocks of sixteen heads, in groups of two."""
    from repro.kernels.ssd_scan import plan_blocks

    assert plan_blocks((1, 384, 64, 64), 128, 256) is None
    assert plan_blocks((1, 384, 64, 64), 128, 256, interpret=True) is None
    assert plan_blocks((8, 2048, 64, 64), 128, 256) == (256, 128, 16, 2, 64, False)
    # Nemotron-H: 8 groups of 8 heads, each group one block of 8 heads.
    assert plan_blocks((4, 2048, 64, 64), 128, 128, groups=8) == (128, 128, 8, 2, 64, False)


@pytest.mark.parametrize("shape", [(4, 100, 256), (3, 384), (2, 7, 512), (1, 128)])
def test_int8_roundtrip(shape):
    x = jax.random.normal(KEY, shape, jnp.float32) * 3
    q, s = quantize_int8_pallas(x, interpret=True)
    qe, se = ref.quantize_int8(x)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qe))
    np.testing.assert_allclose(np.asarray(s), np.asarray(se), rtol=1e-6)
    xr = dequantize_int8_pallas(q, s, interpret=True)
    rel = float(jnp.max(jnp.abs(xr.astype(jnp.float32) - x)) / jnp.max(jnp.abs(x)))
    assert rel < 0.02


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "shape,row_block",
    [
        ((5, 96), 256),     # d=96: gcd clamps the 128 tile to 32
        ((3, 200), 256),    # d=200: gcd clamps the tile to 8
        ((7, 96), 4),       # 7 rows @ row_block 4 -> padded to 8 rows
        ((11, 3, 200), 8),  # folded lead dims: 33 rows -> padded to 40
        ((1, 200), 256),    # single row, clamped tile
    ],
)
def test_int8_awkward_shapes_pallas_matches_ref(shape, row_block, dtype):
    """Pallas <-> oracle parity where the kernel's shape handling works
    hardest: gcd-clamped tiles (d not a multiple of 128) and row counts
    that force the row-padding path. q/scales must match exactly, the
    dequantized output must match the oracle at the requested dtype, and
    the round trip stays inside the standard tolerance."""
    x = (jax.random.normal(KEY, shape, jnp.float32) * 3).astype(dtype)
    q, s = quantize_int8_pallas(x, row_block=row_block, interpret=True)
    qe, se = ref.quantize_int8(x)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qe))
    np.testing.assert_allclose(np.asarray(s), np.asarray(se), rtol=1e-6)

    xr = dequantize_int8_pallas(q, s, dtype=dtype, row_block=row_block,
                                interpret=True)
    xe = ref.dequantize_int8(qe, se, dtype=dtype)
    assert xr.dtype == jnp.dtype(dtype)
    assert xe.dtype == jnp.dtype(dtype)
    np.testing.assert_allclose(np.asarray(xr, np.float32),
                               np.asarray(xe, np.float32),
                               atol=1e-6, rtol=1e-6)
    rel = float(jnp.max(jnp.abs(xr.astype(jnp.float32)
                                - x.astype(jnp.float32)))
                / jnp.max(jnp.abs(x.astype(jnp.float32))))
    assert rel < 0.02


@settings(max_examples=25, deadline=None)
@given(rows=st.integers(1, 9), d=st.integers(1, 260),
       seed=st.integers(0, 2**31 - 1))
def test_int8_roundtrip_property(rows, d, seed):
    """Random (rows, d): Pallas quantize/dequantize agree with the
    oracle bit-for-bit on q/scales and round-trip within rel 2%.
    row_block=4 keeps the padding path exercised whenever rows > 4."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (rows, d),
                          jnp.float32) * 3
    q, s = quantize_int8_pallas(x, row_block=4, interpret=True)
    qe, se = ref.quantize_int8(x)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qe))
    np.testing.assert_allclose(np.asarray(s), np.asarray(se), rtol=1e-6)
    xr = dequantize_int8_pallas(q, s, dtype=jnp.float32, row_block=4,
                                interpret=True)
    rel = float(jnp.max(jnp.abs(xr - x)) / jnp.maximum(jnp.max(jnp.abs(x)),
                                                       1e-8))
    assert rel < 0.02


def test_int8_wire_savings():
    x = jax.random.normal(KEY, (8, 64, 256), jnp.bfloat16)
    q, s = ref.quantize_int8(x)
    wire = q.size * q.dtype.itemsize + s.size * s.dtype.itemsize
    assert wire < 0.6 * x.size * x.dtype.itemsize
