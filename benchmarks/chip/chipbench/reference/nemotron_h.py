"""Plain Nemotron-H block (NVIDIA, arXiv:2504.03624; Nemotron 3 Nano's
config.json for the sizes), one sequence at a time: one period of
``layer_pattern``, each letter one pre-RMSNorm residual branch,
``h = h + mixer(RMSNorm(h))``:

    M  Mamba-2 (arXiv:2405.21060) with B and C in G groups: head h reads
       group h // (H / G); the gated RMSNorm normalizes y * SiLU(z) over
       groups of ``ssm_norm_group`` lanes (d_inner / G)
    *  GQA attention, no positional encoding: softmax(q k^T / sqrt(hd)
       + causal mask) v, query head j reading KV head j // (H / H_kv)
    E  mixture of experts: a sigmoid router over all ``n_experts`` routed
       experts in float32; the top_k are chosen on the sigmoid scores plus
       ``e_score_correction_bias``; their gate weights are the sigmoid
       scores, normalized over the k, times ``routed_scaling``; each
       expert is down(relu(x up)^2); one shared expert of the same form
       is added to the routed sum

The SSD scan is ``mamba2._ssd_quadratic`` per group (the dual form), the
attention ``mistral._attention``. The routed experts are a plain loop
over the experts this chip holds (ids [0, E_held)), each over every
token with a dense mask of the tokens routed to it; experts held on
other chips contribute nothing.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import mamba2, mistral
from chipbench.reference.common import F32, rmsnorm


def _mamba(p, u, c: dict, P):
    """u: (S, D) normed -> (S, D)."""
    g, n = c["ssm_groups"], c["ssm_state"]
    z = P.mm("sd,dhp->shp", u, p["w_z"])
    x = P.mm("sd,dhp->shp", u, p["w_x"])
    B = P.mm("sd,dn->sn", u, p["w_B"])
    C = P.mm("sd,dn->sn", u, p["w_C"])
    dt = P.mm("sd,dh->sh", u, p["w_dt"])
    s, nh, hp = x.shape
    x = mamba2._conv(x.reshape(s, nh * hp), p["conv_x"].reshape(-1, nh * hp),
                     p["conv_x_b"].reshape(nh * hp)).reshape(s, nh, hp)
    B = mamba2._conv(B, p["conv_B"], p["conv_B_b"]).reshape(s, g, n)
    C = mamba2._conv(C, p["conv_C"], p["conv_C_b"]).reshape(s, g, n)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(F32))
    hg = nh // g
    y = jnp.concatenate([
        mamba2._ssd_quadratic(x[:, i * hg:(i + 1) * hg], dt[:, i * hg:(i + 1) * hg],
                              p["A_log"][i * hg:(i + 1) * hg], B[:, i], C[:, i], P)
        for i in range(g)], axis=1)
    y = y + p["D"].astype(F32)[:, None] * x
    lanes = c["ssm_norm_group"]
    gated = (y * jax.nn.silu(z)).reshape(s, -1, lanes)
    gated = rmsnorm(gated, p["norm_scale"].reshape(-1, lanes), c["norm_eps"])
    return P.mm("shp,hpd->sd", gated.reshape(s, nh, hp), p["w_out"])


def _attn(a, x, P):
    q = P.mm("sd,dhk->shk", x, a["wq"])
    k = P.mm("sd,dhk->shk", x, a["wk"])
    v = P.mm("sd,dhk->shk", x, a["wv"])
    return P.mm("shk,hkd->sd", mistral._attention(q, k, v, P), a["wo"])


def _relu2(x, up, down, P):
    return P.mm("sf,fd->sd", jnp.square(jax.nn.relu(P.mm("sd,df->sf", x, up))), down)


def _experts(p, x, c: dict, P):
    """x: (S, D) normed -> (S, D)."""
    scores = jax.nn.sigmoid(P.mm("sd,de->se", x, p["router"]))
    choice = scores + p["e_score_correction_bias"].astype(F32)
    _, ids = jax.lax.top_k(choice, c["top_k"])
    w = jnp.take_along_axis(scores, ids, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * c["routed_scaling"]
    out = _relu2(x, p["shared"]["w_up"], p["shared"]["w_down"], P)
    for e in range(p["w_up"].shape[1]):
        gate = jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1)          # (S,)
        out = out + gate[:, None] * _relu2(x, p["w_up"][:, e], p["w_down"][:, e], P)
    return out


def layer(lp, h, c: dict, P):
    """One block, a period of ``layer_pattern``. lp: this block's params;
    h: (S, D) float32."""
    eps = c["norm_eps"]
    for i, kind in enumerate(c["layer_pattern"]):
        sub = lp[f"sub{i}"]
        if kind == "M":
            h = h + _mamba(sub["mamba"], rmsnorm(h, sub["ln_mixer"]["scale"], eps), c, P)
        elif kind == "*":
            h = h + _attn(sub["attn"], rmsnorm(h, sub["ln_mixer"]["scale"], eps), P)
        else:
            h = h + _experts(sub["experts"], rmsnorm(h, sub["ln_ffn"]["scale"], eps), c, P)
    return h


# -- the count of operations that ``chipbench/counts.py`` composes --------
def layers_per_block(c: dict) -> int:
    """Layers of ``n_layers`` that one scanned block spans: one period."""
    return len(c["layer_pattern"])


def _layer_flops(kind: str, c: dict, s: int):
    """(projection, mixer, input-projection) FLOPs of one layer's forward
    over S tokens."""
    d = c["d_model"]
    if kind == "M":
        h, p, n, g = c["ssm_heads"], c["ssm_headdim"], c["ssm_state"], c["ssm_groups"]
        di, q = h * p, min(c["ssm_chunk"], s)
        in_proj = 2 * d * (2 * di + 2 * g * n + h) * s
        conv = 2 * c["conv_width"] * (di + 2 * g * n) * s
        intra = (2 * g * n + 2 * h * p) * (q + 1) / 2 * s     # causal half, per chunk
        states = 2 * (2 * n * h * p) * s                      # state update and read
        return in_proj + 2 * di * d * s, conv + intra + states, in_proj
    if kind == "*":
        hq, hkv, hd = c["n_heads"], c["n_kv_heads"], c["head_dim"]
        qkv = 2 * d * (hq + 2 * hkv) * hd * s
        return qkv + 2 * hq * hd * d * s, 2 * s * s * hq * hd, qkv
    # E: balanced routing over the held share (counts.py's rule).
    router = 2 * d * c["n_experts"] * s
    routed = c["top_k"] * c["experts_held"] / c["n_experts"] * 2 * (2 * d * c["d_ff"]) * s
    shared = 2 * (2 * d * c["shared_expert_ff"]) * s
    return router + routed + shared, 0, router + routed + shared


def block_flops(c: dict, s: int):
    """(projection FLOPs, mixer FLOPs, input-projection FLOPs) of one
    block's forward over S tokens: its layers summed; the input
    projections are those of its first layer."""
    parts = [_layer_flops(kind, c, s) for kind in c["layer_pattern"]]
    return sum(p[0] for p in parts), sum(p[1] for p in parts), parts[0][2]


def expert_matmul_flops(c: dict, s: int) -> float:
    """The held experts' up and down products in one block's forward over
    S tokens, under balanced routing: what the grouped matmuls compute."""
    per_layer = c["top_k"] * c["experts_held"] / c["n_experts"] * 2 * (2 * c["d_model"]
                                                                      * c["d_ff"]) * s
    return per_layer * c["layer_pattern"].count("E")
