"""Plain Mamba-2 block (Dao & Gu, arXiv:2405.21060), one sequence at a time.

    u = RMSNorm(h)
    z, x, B, C, dt = u W_z, u W_x, u W_B, u W_C, u W_dt       (no biases)
    x, B, C = SiLU(causal depthwise conv_4(x, B, C) + bias)
    dt = softplus(dt + dt_bias),  A = -exp(A_log)
    y_t = sum_{s <= t} exp(sum_{s < r <= t} dt_r A) (C_t . B_s) dt_s x_s + D x_t
    y = RMSNorm_group(y * SiLU(z)) * norm_scale,  h + y W_out

The scan is computed in its quadratic (dual) form, the masked
"attention" matrix of the paper's Sec. 4, one block of query times at a
time; it shares nothing with the program's chunked algorithm. The norm
group is the configuration's ``departures.ssm_norm_group``: the
published model (ngroups = 1) normalizes all ``d_inner`` lanes at once,
the program each head of ``ssm_headdim`` lanes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.common import F32, rmsnorm

Q_BLOCK = 256


def _conv(x, w, b):
    """Causal depthwise conv over time. x: (S, C), w: (W, C); w[W-1]
    multiplies the current step."""
    width, s = w.shape[0], x.shape[0]
    xp = jnp.pad(x, ((width - 1, 0), (0, 0)))
    out = sum(xp[i:i + s] * w[i].astype(F32) for i in range(width))
    return jax.nn.silu(out + b.astype(F32))


def _ssd_quadratic(x, dt, a_log, B, C, P):
    """x: (S, H, P); dt: (S, H); B, C: (S, N) -> y (S, H, P)."""
    s = x.shape[0]
    cum = jnp.cumsum(dt * -jnp.exp(a_log.astype(F32)), axis=0)     # (S, H)
    xdt = x * dt[..., None]
    t_idx = jnp.arange(s)
    q = min(Q_BLOCK, s)

    def block(t0):
        ct = jax.lax.dynamic_slice_in_dim(C, t0, q, 0)              # (T, N)
        cumt = jax.lax.dynamic_slice_in_dim(cum, t0, q, 0)          # (T, H)
        tt = t0 + jnp.arange(q)
        causal = tt[:, None] >= t_idx[None, :]                       # (T, S)
        seg = jnp.where(causal[None], cumt.T[:, :, None] - cum.T[:, None, :], -jnp.inf)
        cb = P.mm("tn,sn->ts", ct, B)
        w = jnp.exp(seg) * cb[None]                                   # (H, T, S)
        return P.mm("hts,shp->thp", w, xdt)

    ys = jax.lax.map(jax.checkpoint(block), jnp.arange(0, s, q))
    return ys.reshape(s, *x.shape[1:])


def layer(lp, h, c: dict, P):
    """One block. lp: this layer's params; h: (S, D) float32."""
    p = lp["sub0"]["mamba"]
    eps = c["norm_eps"]
    u = rmsnorm(h, lp["sub0"]["ln_mixer"]["scale"], eps)
    z = P.mm("sd,dhp->shp", u, p["w_z"])
    x = P.mm("sd,dhp->shp", u, p["w_x"])
    B = P.mm("sd,dn->sn", u, p["w_B"])
    C = P.mm("sd,dn->sn", u, p["w_C"])
    dt = P.mm("sd,dh->sh", u, p["w_dt"])
    s, nh, hp = x.shape
    x = _conv(x.reshape(s, nh * hp), p["conv_x"].reshape(-1, nh * hp),
              p["conv_x_b"].reshape(nh * hp)).reshape(s, nh, hp)
    B = _conv(B, p["conv_B"], p["conv_B_b"])
    C = _conv(C, p["conv_C"], p["conv_C_b"])
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(F32))
    y = _ssd_quadratic(x, dt, p["A_log"], B, C, P) + p["D"].astype(F32)[:, None] * x
    g = y * jax.nn.silu(z)
    if c["departures"]["ssm_norm_group"] == "head":
        g = rmsnorm(g, p["norm_scale"], eps)
    else:
        g = rmsnorm(g.reshape(s, nh * hp), p["norm_scale"].reshape(-1), eps
                    ).reshape(s, nh, hp)
    return h + P.mm("shp,hpd->sd", g, p["w_out"])


# -- the count of operations that ``chipbench/counts.py`` composes --------
def layers_per_block(c: dict) -> int:
    """Layers of ``n_layers`` that one scanned block spans."""
    return 1


def block_flops(c: dict, s: int):
    """(projection FLOPs, mixer FLOPs, input-projection FLOPs) of one
    block's forward over S tokens. The SSD mixer is counted as the
    chunked algorithm with chunk Q: within each chunk the causal half of
    C B^T and of its product with x, and per token one state update and
    one state read (2 N H P each)."""
    d, n, p = c["d_model"], c["ssm_state"], c["ssm_headdim"]
    di = c["ssm_expand"] * d
    h = di // p
    q = min(c["ssm_chunk"], s)
    w = c["conv_width"]
    in_proj = 2 * d * (2 * di + 2 * n + h) * s
    out_proj = 2 * di * d * s
    conv = 2 * w * (di + 2 * n) * s
    intra = (2 * n + 2 * h * p) * (q + 1) / 2 * s     # causal half, per chunk
    states = 2 * (2 * n * h * p) * s
    return in_proj + out_proj, conv + intra + states, in_proj
