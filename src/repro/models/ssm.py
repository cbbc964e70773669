"""Mamba2 (state-space duality) blocks — chunked scan + O(1)-state decode.

Implements the SSD algorithm of arXiv:2405.21060 in its chunked matrix
form: within-chunk attention-like term + inter-chunk state recurrence.
All decay products are computed in log space (A < 0 so products <= 1).

TP layout (DESIGN.md §4/§5): projections are split per component with the
head dimension exposed — ``w_z/w_x: (D, H, P)``, ``w_dt: (D, H)``,
``w_out: (H, P, D)`` — so heads shard cleanly over the ``model`` mesh axis
(SSD is per-head; B/C are replicated; the only cross-shard reduction is
the out-projection's standard TP all-reduce). B and C come in
``ssm_groups`` groups of ``ssm_state`` lanes, flattened to ``(D, G*N)``
(at one group mamba2's ``(D, N)``); head h reads group h // (H / G). The
gated RMSNorm runs over groups of ``ssm_norm_lanes`` lanes: one head for
mamba2 (shard-local), d_inner / G lanes for Nemotron-H.

The chunked scan itself runs through ``kernels.ops.ssd_scan``: the Pallas
kernel pair of ``repro.kernels.ssd_scan`` on TPU, its XLA twin
``kernels.ref.ssd_chunked`` elsewhere.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.kernels import ops
from repro.models.layers import causal_conv1d
from repro.models.module import dense_init, dtype_of, zeros_init


class MambaCache(NamedTuple):
    conv_x: jnp.ndarray  # (B, W-1, H, P)
    conv_B: jnp.ndarray  # (B, W-1, G*N)
    conv_C: jnp.ndarray  # (B, W-1, G*N)
    ssm: jnp.ndarray     # (B, H, N, P) — recurrent state (f32)


def ssm_init(key, cfg: ModelConfig) -> dict:
    dt = dtype_of(cfg.param_dtype)
    d, h, p = cfg.d_model, cfg.ssm_nheads, cfg.ssm_headdim
    n = cfg.ssm_groups * cfg.ssm_state
    ks = jax.random.split(key, 8)
    return {
        "w_z": dense_init(ks[0], d, (h, p), dt),
        "w_x": dense_init(ks[1], d, (h, p), dt),
        "w_B": dense_init(ks[2], d, (n,), dt),
        "w_C": dense_init(ks[3], d, (n,), dt),
        "w_dt": dense_init(ks[4], d, (h,), dt),
        "conv_x": (jax.random.normal(ks[5], (cfg.conv_width, h, p)) * 0.1).astype(dt),
        "conv_x_b": zeros_init((h, p), dt),
        "conv_B": (jax.random.normal(ks[6], (cfg.conv_width, n)) * 0.1).astype(dt),
        "conv_B_b": zeros_init((n,), dt),
        "conv_C": (jax.random.normal(ks[7], (cfg.conv_width, n)) * 0.1).astype(dt),
        "conv_C_b": zeros_init((n,), dt),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, h)).astype(jnp.float32),
        "D": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(jnp.full((h,), 0.01))).astype(jnp.float32),
        "norm_scale": jnp.ones((h, p), dt),
        "w_out": dense_init(jax.random.fold_in(key, 9), h * p, (d,), dt).reshape(h, p, d),
    }


def _group_rmsnorm(scale, y, lanes: int, eps: float):
    """RMSNorm over groups of ``lanes`` consecutive lanes of the heads
    (mamba2's grouped RMSNorm). y: (..., H, P); scale: (H, P)."""
    y32 = y.astype(jnp.float32)
    g = y32.reshape(*y.shape[:-2], -1, lanes)
    var = jnp.mean(jnp.square(g), axis=-1, keepdims=True)
    g = (g * jax.lax.rsqrt(var + eps)).reshape(y.shape)
    return (g * scale.astype(jnp.float32)).astype(y.dtype)


def bc_groups(bc, cfg: ModelConfig):
    """B or C, (..., G*N), as (..., G, N)."""
    return bc.reshape(*bc.shape[:-1], cfg.ssm_groups, cfg.ssm_state)


def _project(params, u, cfg: ModelConfig):
    """u: (B,S,D) -> z,x: (B,S,H,P); B,C: (B,S,G*N); dt: (B,S,H) (pre-conv)."""
    z = jnp.einsum("bsd,dhp->bshp", u, params["w_z"])
    x = jnp.einsum("bsd,dhp->bshp", u, params["w_x"])
    B_ = jnp.einsum("bsd,dn->bsn", u, params["w_B"])
    C_ = jnp.einsum("bsd,dn->bsn", u, params["w_C"])
    dt = jnp.einsum("bsd,dh->bsh", u, params["w_dt"])
    return z, x, B_, C_, dt


def _conv_all(params, x, B_, C_, cfg: ModelConfig):
    b, s, h, p = x.shape
    xf = causal_conv1d(params["conv_x"].reshape(cfg.conv_width, h * p),
                       x.reshape(b, s, h * p))
    x = jax.nn.silu(xf.reshape(b, s, h, p) + params["conv_x_b"])
    B_ = jax.nn.silu(causal_conv1d(params["conv_B"], B_) + params["conv_B_b"])
    C_ = jax.nn.silu(causal_conv1d(params["conv_C"], C_) + params["conv_C_b"])
    return x, B_, C_


def _ssd_core(params, u, cfg: ModelConfig, init_state=None):
    b, s, _ = u.shape
    z, x, B_, C_, dt = _project(params, u, cfg)
    raw_x_tail = None
    if cfg.conv_width > 1:
        raw_x_tail = (
            x[:, s - (cfg.conv_width - 1):],
            B_[:, s - (cfg.conv_width - 1):],
            C_[:, s - (cfg.conv_width - 1):],
        )
    x, B_, C_ = _conv_all(params, x, B_, C_, cfg)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])
    y, state = ops.ssd_scan(x, dt * A, dt, bc_groups(B_, cfg), bc_groups(C_, cfg),
                            params["D"], init_state, chunk=cfg.ssm_chunk)
    y = _group_rmsnorm(params["norm_scale"], y.astype(u.dtype) * jax.nn.silu(z),
                       cfg.ssm_norm_lanes, cfg.norm_eps)
    out = jnp.einsum("bshp,hpd->bsd", y, params["w_out"])
    return out, state, raw_x_tail


def ssm_apply(params, u: jnp.ndarray, cfg: ModelConfig):
    """Full-sequence Mamba2 mixer. u: (B, S, D) -> (B, S, D)."""
    out, _, _ = _ssd_core(params, u, cfg)
    return out


def ssm_prefill(params, u, cfg: ModelConfig):
    """Full-sequence mixer that also returns the decode cache."""
    out, state, (xt, bt, ct) = _ssd_core(params, u, cfg)
    cache = MambaCache(
        conv_x=xt.astype(jnp.bfloat16),
        conv_B=bt.astype(jnp.bfloat16),
        conv_C=ct.astype(jnp.bfloat16),
        ssm=state,
    )
    return out, cache


def ssm_init_cache(cfg: ModelConfig, batch: int, dtype=jnp.bfloat16) -> MambaCache:
    n, h, p, w = cfg.ssm_state, cfg.ssm_nheads, cfg.ssm_headdim, cfg.conv_width
    gn = cfg.ssm_groups * n
    return MambaCache(
        conv_x=jnp.zeros((batch, w - 1, h, p), dtype),
        conv_B=jnp.zeros((batch, w - 1, gn), dtype),
        conv_C=jnp.zeros((batch, w - 1, gn), dtype),
        ssm=jnp.zeros((batch, h, n, p), jnp.float32),
    )


def ssm_decode(params, u, cache: MambaCache, cfg: ModelConfig):
    """Single-token recurrent step. u: (B, 1, D)."""
    b = u.shape[0]
    g, h, p = cfg.ssm_groups, cfg.ssm_nheads, cfg.ssm_headdim
    z, x_new, B_new, C_new, dt = _project(params, u, cfg)

    def roll(state, new, wgt, bias):
        # state: (B, W-1, ...), new: (B, 1, ...) -> conv output (B, ...)
        win = jnp.concatenate([state.astype(new.dtype), new], axis=1)
        out = jnp.einsum(
            "bw...,w...->b...", win.astype(jnp.float32), wgt.astype(jnp.float32)
        ) + bias.astype(jnp.float32)
        return jax.nn.silu(out), win[:, 1:]

    x, new_cx = roll(cache.conv_x, x_new, params["conv_x"], params["conv_x_b"])
    B_, new_cb = roll(cache.conv_B, B_new, params["conv_B"], params["conv_B_b"])
    C_, new_cc = roll(cache.conv_C, C_new, params["conv_C"], params["conv_C_b"])

    dt = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + params["dt_bias"])  # (B,H)
    A = -jnp.exp(params["A_log"])
    a = jnp.exp(dt * A)                                                     # (B,H)

    dx = (x * dt[..., None]).reshape(b, g, h // g, p)                      # (B,G,H/G,P)
    new_state = cache.ssm * a[:, :, None, None] + jnp.einsum(
        "bgn,bghp->bghnp", bc_groups(B_, cfg), dx
    ).reshape(cache.ssm.shape)
    y = jnp.einsum("bgn,bghnp->bghp", bc_groups(C_, cfg),
                   new_state.reshape(b, g, h // g, *new_state.shape[2:])).reshape(b, h, p)
    y = y + params["D"][None, :, None] * x
    y = y[:, None].astype(u.dtype)                                          # (B,1,H,P)
    y = _group_rmsnorm(params["norm_scale"], y * jax.nn.silu(z), cfg.ssm_norm_lanes,
                       cfg.norm_eps)
    out = jnp.einsum("bshp,hpd->bsd", y, params["w_out"])
    new_cache = MambaCache(
        conv_x=new_cx.astype(cache.conv_x.dtype),
        conv_B=new_cb.astype(cache.conv_B.dtype),
        conv_C=new_cc.astype(cache.conv_C.dtype),
        ssm=new_state,
    )
    return out, new_cache
