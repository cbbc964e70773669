"""Mamba2 SSD chunked-scan Pallas kernel.

Grid = (B, head_blocks, n_chunks), chunks minor-most: the (hb, N, P)
recurrent state lives in VMEM scratch across the chunk sweep — the HBM
traffic per chunk is exactly the chunk's inputs + outputs (the XLA twin
re-materializes cumsums and decay matrices through fusion boundaries).
Within a chunk everything is the SSD matrix form: decay matrix L from a
log-space cumulative sum, C B^T Hadamard L for the diagonal term, carried
state for the off-diagonal term, state update via decay-to-end weights.

Layout: the wrapper puts heads ahead of sequence — x as (B, H, S, P),
the per-step log decays and dt scales as (B, H, S) — so every block's
last two dims are (chunk, P), (hb, chunk) or (chunk, N): multiples of
the (8, 128) tile or full array dims for hb = 8. Mosaic has no cumsum,
so the wrapper also takes the chunk-local cumulative sum of the log
decays (an O(B*S*H) XLA op); the kernel reads it directly.

Head-blocked so that VMEM holds (Q x Q) decay tiles per head plus the
(hb, N, P) state: hb = 8 heads of P=64 at N=128 -> 0.25 MiB state,
(256 x 256) tiles -> 0.25 MiB each.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, cum_ref, dts_ref, b_ref, c_ref, y_ref, state_out_ref,
                state_ref, *, n_chunks: int, hb: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    cum = cum_ref[0].astype(jnp.float32)    # (hb, Q) chunk-local cumsum
    dts = dts_ref[0].astype(jnp.float32)    # (hb, Q)
    B_ = b_ref[0].astype(jnp.float32)       # (Q, N)
    C_ = c_ref[0].astype(jnp.float32)       # (Q, N)

    q = B_.shape[0]
    cb = jax.lax.dot_general(
        C_, B_, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                                        # (Q, Q)
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    tri = ii >= jj

    for h in range(hb):  # static unroll over the head block
        row = cum[h:h + 1, :]                                # (1, Q)
        col = row.reshape(q, 1)                              # (Q, 1)
        last = col[q - 1:q, :]                               # (1, 1)
        xs = x_ref[0, h].astype(jnp.float32) * dts[h:h + 1, :].reshape(q, 1)
        Lh = jnp.where(tri, jnp.exp(col - row), 0.0)         # (Q, Q)
        y_diag = jax.lax.dot_general(
            cb * Lh, xs, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                    # (Q, P)
        state_h = state_ref[h]                               # (N, P)
        y_off = jax.lax.dot_general(
            C_ * jnp.exp(col), state_h, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                    # (Q, P)
        y_ref[0, h] = (y_diag + y_off).astype(y_ref.dtype)

        b_end = (B_ * jnp.exp(last - col)).T                 # (N, Q)
        s_chunk = jax.lax.dot_general(
            b_end, xs, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                    # (N, P)
        state_ref[h] = state_h * jnp.exp(last) + s_chunk

    @pl.when(ci == n_chunks - 1)
    def _emit_state():
        state_out_ref[0] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "head_block", "interpret"))
def ssd_scan_pallas(
    x: jnp.ndarray,      # (B, S, H, P)
    dtA: jnp.ndarray,    # (B, S, H)
    dt: jnp.ndarray,     # (B, S, H)
    B_: jnp.ndarray,     # (B, S, N)
    C_: jnp.ndarray,     # (B, S, N)
    init_state=None,     # must be None (kernel owns state init)
    *,
    chunk: int = 256,
    head_block: int = 8,
    interpret: bool = False,
):
    assert init_state is None, "pallas ssd owns the state"
    b, s, h, p = x.shape
    n = B_.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    hb = min(head_block, h)
    assert h % hb == 0, (h, hb)
    n_chunks = s // chunk
    grid = (b, h // hb, n_chunks)

    # Heads ahead of sequence; log decays summed within each chunk.
    xt = x.transpose(0, 2, 1, 3)                                  # (B,H,S,P)
    cum = jnp.cumsum(dtA.astype(jnp.float32).reshape(b, n_chunks, chunk, h),
                     axis=2).reshape(b, s, h).transpose(0, 2, 1)  # (B,H,S)
    dts = dt.transpose(0, 2, 1)                                   # (B,H,S)

    kernel = functools.partial(_ssd_kernel, n_chunks=n_chunks, hb=hb)
    y, state = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, hb, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, hb, chunk), lambda bi, hi, ci: (bi, hi, ci)),
            pl.BlockSpec((1, hb, chunk), lambda bi, hi, ci: (bi, hi, ci)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, hb, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, hb, n, p), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hb, n, p), jnp.float32)],
        interpret=interpret,
    )(xt, cum, dts, B_, C_)
    return y.transpose(0, 2, 1, 3), state
