"""Flash attention (fwd) Pallas TPU kernel.

Canonical TPU tiling: grid = (batch*heads, q_blocks, kv_blocks), kv minor-
most so the VMEM scratch accumulators (m, l, acc) persist across the kv
sweep of one q block. Block shapes are MXU-aligned (q_block x head_dim and
kv_block x head_dim tiles, multiples of 128 on the minor dim for bf16).
Causal blocks fully above the diagonal are skipped with pl.when (the 2x
triangle saving the XLA twin cannot express).

Validated against repro.kernels.ref.flash_attention in interpret mode
(CPU); on TPU the same pallas_call lowers to Mosaic.
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


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  q_block: int, kv_block: int, n_kv: int, causal: bool,
                  window: Optional[int], softcap: Optional[float],
                  scale: float, seq_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * q_block
    kv_start = ki * kv_block

    def _compute():
        q = q_ref[0].astype(jnp.float32)                    # (qb, hd)
        k = k_ref[0].astype(jnp.float32)                    # (kvb, hd)
        v = v_ref[0].astype(jnp.float32)
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                           # (qb, kvb)
        if softcap is not None:
            scores = softcap * jnp.tanh(scores / softcap)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (q_block, kv_block), 0)
        kpos = kv_start + jax.lax.broadcasted_iota(jnp.int32, (q_block, kv_block), 1)
        mask = kpos < seq_len
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window - 1
        scores = jnp.where(mask, scores, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, scores.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new[:, None])
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    if causal:
        # Skip blocks strictly above the diagonal.
        pl.when(kv_start <= q_start + q_block - 1)(_compute)
    elif window is not None:
        live = (kv_start <= q_start + q_block - 1) & (
            kv_start + kv_block - 1 > q_start - window - 1
        )
        pl.when(live)(_compute)
    else:
        _compute()

    @pl.when(ki == n_kv - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "q_block", "kv_block", "interpret"),
)
def flash_attention_pallas(
    q: jnp.ndarray,  # (B, S, H, hd)
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    q_block: int = 256,
    kv_block: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    b, s, h, hd = q.shape
    q_block = min(q_block, s)
    kv_block = min(kv_block, s)
    # Pad sequence to a block multiple (mask handles the tail).
    s_pad = math.ceil(s / max(q_block, kv_block)) * max(q_block, kv_block)
    if s_pad != s:
        pad = ((0, 0), (0, s_pad - s), (0, 0), (0, 0))
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)

    qt = q.transpose(0, 2, 1, 3).reshape(b * h, s_pad, hd)
    kt = k.transpose(0, 2, 1, 3).reshape(b * h, s_pad, hd)
    vt = v.transpose(0, 2, 1, 3).reshape(b * h, s_pad, hd)

    n_q = s_pad // q_block
    n_kv = s_pad // kv_block
    grid = (b * h, n_q, n_kv)
    scale = 1.0 / math.sqrt(hd)

    kernel = functools.partial(
        _flash_kernel, q_block=q_block, kv_block=kv_block, n_kv=n_kv,
        causal=causal, window=window, softcap=softcap, scale=scale, seq_len=s,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q_block, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, kv_block, hd), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, kv_block, hd), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_block, hd), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s_pad, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block,), jnp.float32),   # running max m
            pltpu.VMEM((q_block,), jnp.float32),   # running sum l
            pltpu.VMEM((q_block, hd), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
    )(qt, kt, vt)
    out = out.reshape(b, h, s_pad, hd).transpose(0, 2, 1, 3)
    return out[:, :s]
