"""Int8 split-activation compression Pallas kernels (beyond-paper).

The tier boundary's wire bytes are THE knob of the paper's cost model
(l_split). These kernels quantize the boundary activations to int8 with
per-128-lane scales right where they leave the storage tier, and
dequantize on the compute tier: exactly
``ops.compression_ratio(dtype, tile)`` of the raw bytes on the
bottleneck link — (1 + 4/128)/2 = 0.515625x for bf16 with the default
128 tile. Tiles are (rows x 128) — one scale per VREG lane group, so
the abs-max reduction and the scaled cast both vectorize cleanly.

Compiled (``interpret=False``, the TPU path) the feature width must be a
multiple of 128 lanes: the in-kernel (rows, D/128, 128) reshape is the
only form Mosaic lays out. Interpret mode keeps the ``gcd(d, tile)``
clamp for narrow widths.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


LANES = 128


def _check_lanes(d: int, tile: int, interpret: bool) -> None:
    if not interpret and (d % LANES or tile % LANES):
        raise ValueError(
            f"the compiled int8 kernels need the feature width and tile to be "
            f"multiples of {LANES} lanes, got d={d}, tile={tile}; pad the "
            f"width or use interpret mode off-TPU")


def _quant_kernel(x_ref, q_ref, s_ref, *, tile: int):
    x = x_ref[...].astype(jnp.float32)              # (rows, D)
    rows, d = x.shape
    xt = x.reshape(rows, d // tile, tile)
    amax = jnp.max(jnp.abs(xt), axis=-1)            # (rows, D/tile)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(xt / scale[..., None]), -127, 127)
    q_ref[...] = q.reshape(rows, d).astype(jnp.int8)
    s_ref[...] = scale


def _dequant_kernel(q_ref, s_ref, x_ref, *, tile: int):
    q = q_ref[...].astype(jnp.float32)
    rows, d = q.shape
    x = q.reshape(rows, d // tile, tile) * s_ref[...][..., None]
    x_ref[...] = x.reshape(rows, d).astype(x_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "row_block", "interpret"))
def quantize_int8_pallas(x: jnp.ndarray, *, tile: int = 128,
                         row_block: int = 256, interpret: bool = False):
    *lead, d = x.shape
    tile = math.gcd(d, tile)
    _check_lanes(d, tile, interpret)
    rows = int(math.prod(lead)) if lead else 1
    xf = x.reshape(rows, d)
    rb = min(row_block, rows)
    rows_pad = math.ceil(rows / rb) * rb
    if rows_pad != rows:
        xf = jnp.pad(xf, ((0, rows_pad - rows), (0, 0)))

    q, s = pl.pallas_call(
        functools.partial(_quant_kernel, tile=tile),
        grid=(rows_pad // rb,),
        in_specs=[pl.BlockSpec((rb, d), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((rb, d), lambda i: (i, 0)),
            pl.BlockSpec((rb, d // tile), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows_pad, d), jnp.int8),
            jax.ShapeDtypeStruct((rows_pad, d // tile), jnp.float32),
        ],
        interpret=interpret,
    )(xf)
    q = q[:rows].reshape(*lead, d)
    s = s[:rows].reshape(*lead, d // tile)
    return q, s


@functools.partial(jax.jit, static_argnames=("dtype", "row_block", "interpret"))
def dequantize_int8_pallas(q: jnp.ndarray, scales: jnp.ndarray, *,
                           dtype=jnp.bfloat16,
                           row_block: int = 256, interpret: bool = False):
    *lead, d = q.shape
    tile = d // scales.shape[-1]
    _check_lanes(d, tile, interpret)
    rows = int(math.prod(lead)) if lead else 1
    qf = q.reshape(rows, d)
    sf = scales.reshape(rows, d // tile)
    rb = min(row_block, rows)
    rows_pad = math.ceil(rows / rb) * rb
    if rows_pad != rows:
        qf = jnp.pad(qf, ((0, rows_pad - rows), (0, 0)))
        sf = jnp.pad(sf, ((0, rows_pad - rows), (0, 0)))

    x = pl.pallas_call(
        functools.partial(_dequant_kernel, tile=tile),
        grid=(rows_pad // rb,),
        in_specs=[
            pl.BlockSpec((rb, d), lambda i: (i, 0)),
            pl.BlockSpec((rb, d // tile), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rb, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_pad, d), dtype),
        interpret=interpret,
    )(qf, sf)
    return x[:rows].reshape(*lead, d)
