"""Trip-count-aware HLO cost analysis (the roofline instrument)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.hlo_analysis import analyze_hlo, parse_module


def _compile(f, *specs):
    return jax.jit(f).lower(*specs).compile()


def test_scan_unroll_parity():
    def f_scan(x, w):
        def body(h, _):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(body, x, None, length=10)
        return h

    def f_unroll(x, w):
        h = x
        for _ in range(10):
            h = jnp.tanh(h @ w)
        return h

    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    cs = _compile(f_scan, x, w)
    cu = _compile(f_unroll, x, w)
    fs = analyze_hlo(cs.as_text()).flops
    fu = analyze_hlo(cu.as_text()).flops
    expected = 10 * 2 * 128 * 256 * 256
    assert abs(fs - expected) / expected < 0.05
    assert abs(fu - expected) / expected < 0.05
    # XLA's own count misses the trip count
    assert cs.cost_analysis()["flops"] < 0.2 * expected


def test_nested_scan_multiplies():
    def f(x, w):
        def outer(h, _):
            def inner(g, _):
                return g @ w, None
            g, _ = jax.lax.scan(inner, h, None, length=4)
            return g, None
        h, _ = jax.lax.scan(outer, x, None, length=3)
        return h

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    c = _compile(f, x, w)
    flops = analyze_hlo(c.as_text()).flops
    expected = 12 * 2 * 64 * 64 * 64
    assert abs(flops - expected) / expected < 0.05


def test_dot_contract_dims():
    def f(a, b):
        return jnp.einsum("bij,bjk->bik", a, b)

    a = jax.ShapeDtypeStruct((4, 32, 64), jnp.float32)
    b = jax.ShapeDtypeStruct((4, 64, 16), jnp.float32)
    c = _compile(f, a, b)
    flops = analyze_hlo(c.as_text()).flops
    expected = 2 * 4 * 32 * 16 * 64
    assert abs(flops - expected) / expected < 0.05


def test_bytes_accounting_positive():
    def f(a, b):
        return (a @ b).sum()

    a = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    c = _compile(f, a, a)
    hc = analyze_hlo(c.as_text())
    assert hc.bytes >= 3 * 256 * 256 * 4 * 0.9  # two reads + one write


def test_parse_module_finds_entry():
    def f(x):
        return x * 2

    c = _compile(f, jax.ShapeDtypeStruct((8,), jnp.float32))
    comps, entry = parse_module(c.as_text())
    assert entry is not None and entry in comps
