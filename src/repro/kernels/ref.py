"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth the kernels are validated
against (interpret=True on CPU, real lowering on TPU). They are also the
fallback implementation ops.py dispatches to on non-TPU backends.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Flash attention (fwd) oracle
# ---------------------------------------------------------------------------
def flash_attention(
    q: jnp.ndarray,  # (B, S, H, hd)
    k: jnp.ndarray,  # (B, S, H, hd) — KV already repeated to H
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
) -> jnp.ndarray:
    b, s, h, hd = q.shape
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        scores = softcap * jnp.tanh(scores / softcap)
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(s)[None, :]
    mask = jnp.ones((s, s), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window - 1
    scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# ---------------------------------------------------------------------------
# GQA decode attention oracle
# ---------------------------------------------------------------------------
def decode_attention(
    q: jnp.ndarray,        # (B, Hq, hd) — one token
    k_cache: jnp.ndarray,  # (B, S, Hkv, hd)
    v_cache: jnp.ndarray,
    length: jnp.ndarray,   # scalar — valid cache length (positions < length)
    *,
    softcap: Optional[float] = None,
) -> jnp.ndarray:
    b, hq, hd = q.shape
    hkv = k_cache.shape[2]
    rep = hq // hkv
    s = k_cache.shape[1]
    qg = q.reshape(b, hkv, rep, hd)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    scores = jnp.einsum(
        "bhrd,bshd->bhrs", qg, k_cache, preferred_element_type=jnp.float32
    ) * scale
    if softcap is not None:
        scores = softcap * jnp.tanh(scores / softcap)
    valid = jnp.arange(s)[None, None, None, :] < length
    scores = jnp.where(valid, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhrs,bshd->bhrd", probs.astype(v_cache.dtype), v_cache)
    return out.reshape(b, hq, hd)


# ---------------------------------------------------------------------------
# Mamba2 SSD chunk oracle — sequential recurrence (ground truth)
# ---------------------------------------------------------------------------
def ssd_reference(
    x: jnp.ndarray,          # (B, S, H, P)
    dtA: jnp.ndarray,        # (B, S, H) log decay
    dt: jnp.ndarray,         # (B, S, H) input scale
    B_: jnp.ndarray,         # (B, S, G, N)
    C_: jnp.ndarray,         # (B, S, G, N)
    init_state: Optional[jnp.ndarray] = None,  # (B, H, N, P)
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Head h reads B/C group h // (H / G)."""
    b, s, h, p = x.shape
    g, n = B_.shape[-2:]
    if init_state is None:
        init_state = jnp.zeros((b, h, n, p), jnp.float32)

    def step(state, inp):
        xt, at, dtt, bt, ct = inp
        a = jnp.exp(at)[:, :, None, None]                        # (B,H,1,1)
        xg = (xt * dtt[..., None]).reshape(b, g, h // g, p)
        upd = jnp.einsum("bgn,bghp->bghnp", bt, xg).reshape(b, h, n, p)
        state = state * a + upd
        y = jnp.einsum("bgn,bghnp->bghp", ct, state.reshape(b, g, h // g, n, p))
        return state, y.reshape(b, h, p)

    xs = (
        jnp.moveaxis(x.astype(jnp.float32), 1, 0),
        jnp.moveaxis(dtA.astype(jnp.float32), 1, 0),
        jnp.moveaxis(dt.astype(jnp.float32), 1, 0),
        jnp.moveaxis(B_.astype(jnp.float32), 1, 0),
        jnp.moveaxis(C_.astype(jnp.float32), 1, 0),
    )
    final, ys = jax.lax.scan(step, init_state, xs)
    return jnp.moveaxis(ys, 0, 1), final


def ssd_chunked(x, dtA, dtx_scale, B, C, init_state=None, chunk: int = 256):
    """Chunked SSD scan in XLA: the twin of the Pallas pair in
    ``ssd_scan.py``, and the path off TPU.

    x:   (B, S, H, P)    head inputs
    dtA: (B, S, H)       log-decay per step (= dt * A, A < 0)
    dtx_scale: (B, S, H) dt multiplier applied to inputs
    B,C: (B, S, G, N)    input/output projections in G groups; head h
                         reads group h // (H / G)
    Returns (y (B,S,H,P), final_state (B,H,N,P)).
    """
    b, s, h, p = x.shape
    g, n = B.shape[-2:]
    hg = h // g
    q = min(chunk, s)
    assert s % q == 0
    nc = s // q

    # One chunk in flight at a time (scan over chunks): the working set is
    # O(B*Q*Q*H) instead of O(B*S*Q*H).
    xc = jnp.moveaxis(x.reshape(b, nc, q, h, p), 1, 0)
    dtAc = jnp.moveaxis(dtA.reshape(b, nc, q, h).astype(jnp.float32), 1, 0)
    dtsc = jnp.moveaxis(dtx_scale.reshape(b, nc, q, h).astype(jnp.float32), 1, 0)
    Bc = jnp.moveaxis(B.reshape(b, nc, q, g, n), 1, 0)
    Cc = jnp.moveaxis(C.reshape(b, nc, q, g, n), 1, 0)

    if init_state is None:
        init_state = jnp.zeros((b, h, n, p), jnp.float32)
    tri = jnp.tril(jnp.ones((q, q), bool))
    per_group = lambda a: a.reshape(*a.shape[:-1], g, hg)          # (..., H) -> (..., G, H/G)

    def chunk_step(state, inp):
        xk, ak, dk, bk, ck = inp                           # (B,Q,...)
        cum = jnp.cumsum(ak, axis=1)                       # (B,Q,H)
        # Within-chunk decay L[i,j] = exp(cum_i - cum_j), i >= j. Mask the
        # exponent, not its result: above the diagonal cum_i - cum_j > 0
        # overflows to inf for strong decays, and exp's gradient there
        # (0 * inf) would be NaN.
        seg = jnp.where(tri[None, :, :, None],
                        cum[:, :, None, :] - cum[:, None, :, :], -jnp.inf)
        Lmat = jnp.exp(seg)                                # (B,Q,Q,H)
        cb = jnp.einsum("bqgn,bkgn->bqkg", ck, bk, preferred_element_type=jnp.float32)
        scores = per_group(Lmat) * cb[..., None]           # (B,Q,Q,G,H/G)
        xs = xk.astype(jnp.float32) * dk[..., None]        # dt-scaled inputs
        y_diag = jnp.einsum("bqkgh,bkghp->bqghp", scores,
                            xs.reshape(b, q, g, hg, p))
        # Carried-state contribution.
        decay_in = jnp.exp(cum)                            # (B,Q,H)
        state_g = state.reshape(b, g, hg, n, p)
        y_off = jnp.einsum(
            "bqgn,bghnp,bqgh->bqghp", ck.astype(jnp.float32), state_g, per_group(decay_in)
        )
        # State update.
        decay_to_end = jnp.exp(cum[:, -1:, :] - cum)       # (B,Q,H)
        s_chunk = jnp.einsum(
            "bqgn,bqgh,bqghp->bghnp", bk.astype(jnp.float32), per_group(decay_to_end),
            xs.reshape(b, q, g, hg, p)
        ).reshape(b, h, n, p)
        new_state = state * jnp.exp(cum[:, -1, :])[:, :, None, None] + s_chunk
        return new_state, (y_diag + y_off).reshape(b, q, h, p)

    final_state, ys = jax.lax.scan(chunk_step, init_state, (xc, dtAc, dtsc, Bc, Cc))
    y = jnp.moveaxis(ys, 0, 1).reshape(b, s, h, p)
    return y, final_state


# ---------------------------------------------------------------------------
# Int8 boundary compression oracle
# ---------------------------------------------------------------------------
def quantize_int8(x: jnp.ndarray, tile: int = 128):
    """Per-tile symmetric int8 quantization over the last dim.
    Returns (q int8 (..., D), scales f32 (..., D/tile))."""
    import math

    *lead, d = x.shape
    tile = math.gcd(d, tile)  # clamp for narrow (smoke) widths
    xt = x.reshape(*lead, d // tile, tile).astype(jnp.float32)
    amax = jnp.max(jnp.abs(xt), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(xt / scale), -127, 127).astype(jnp.int8)
    return q.reshape(*lead, d), scale[..., 0]


def dequantize_int8(q: jnp.ndarray, scales: jnp.ndarray, dtype=jnp.bfloat16):
    *lead, d = q.shape
    tile = d // scales.shape[-1]
    qt = q.reshape(*lead, d // tile, tile).astype(jnp.float32)
    x = qt * scales[..., None]
    return x.reshape(*lead, d).astype(dtype)
