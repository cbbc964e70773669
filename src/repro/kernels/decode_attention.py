"""GQA single-token decode attention Pallas kernel (flash-decode).

One new query token attends over a long KV cache. Grid = (B, Hkv,
s_blocks) with the cache-sequence axis minor-most; the (rep, hd) VMEM
accumulators persist across the sweep, so arbitrarily long caches stream
through VMEM in s_block tiles. All ``rep`` query heads of a KV group are
processed together — the MXU tile is (rep x hd) x (hd x s_block), which
is why GQA decode wants the group dim collapsed into the matmul.

The valid-length mask comes from a scalar operand (SMEM) so the same
compiled kernel serves any cache fill level.

Layout: the wrapper puts KV heads ahead of the cache sequence, (B, Hkv,
S, hd), so a cache block's last two dims are (s_block, hd) — multiples
of the (8, 128) tile — rather than (1, hd).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                   *, s_block: int, n_s: int, softcap: Optional[float],
                   scale: float):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[0]
    s_start = si * s_block

    @pl.when(s_start < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                 # (rep, hd)
        k = k_ref[0, 0].astype(jnp.float32)                 # (sb, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                           # (rep, sb)
        if softcap is not None:
            scores = softcap * jnp.tanh(scores / softcap)
        pos = s_start + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        scores = jnp.where(pos < length, scores, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, scores.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new[:, None])
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(si == n_s - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("softcap", "s_block", "interpret")
)
def decode_attention_pallas(
    q: jnp.ndarray,        # (B, Hq, hd)
    k_cache: jnp.ndarray,  # (B, S, Hkv, hd)
    v_cache: jnp.ndarray,
    length,                # scalar int32: valid cache prefix
    *,
    softcap: Optional[float] = None,
    s_block: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    b, hq, hd = q.shape
    _, s, hkv, _ = k_cache.shape
    rep = hq // hkv
    s_block = min(s_block, s)
    s_pad = math.ceil(s / s_block) * s_block
    if s_pad != s:
        pad = ((0, 0), (0, s_pad - s), (0, 0), (0, 0))
        k_cache, v_cache = jnp.pad(k_cache, pad), jnp.pad(v_cache, pad)

    qg = q.reshape(b, hkv, rep, hd)
    kt = k_cache.transpose(0, 2, 1, 3)                       # (B, Hkv, S, hd)
    vt = v_cache.transpose(0, 2, 1, 3)
    n_s = s_pad // s_block
    grid = (b, hkv, n_s)
    scale = 1.0 / math.sqrt(hd)
    length_arr = jnp.asarray(length, jnp.int32).reshape(1)

    kernel = functools.partial(
        _decode_kernel, s_block=s_block, n_s=n_s, softcap=softcap, scale=scale
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, rep, hd), lambda bi, hi, si: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, s_block, hd), lambda bi, hi, si: (bi, hi, si, 0)),
            pl.BlockSpec((1, 1, s_block, hd), lambda bi, hi, si: (bi, hi, si, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, hd), lambda bi, hi, si: (bi, hi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, rep, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((rep,), jnp.float32),
            pltpu.VMEM((rep,), jnp.float32),
            pltpu.VMEM((rep, hd), jnp.float32),
        ],
        interpret=interpret,
    )(length_arr, qg, kt, vt)
    return out.reshape(b, hq, hd)
