"""Plain Mistral decoder block (GQA, RoPE, SwiGLU, RMSNorm), one sequence
at a time, from the published architecture (Jiang et al.,
arXiv:2310.06825; Mistral-Nemo's config.json for the sizes).

    x = RMSNorm(h);  q, k, v = x Wq, x Wk, x Wv     (no biases)
    q, k = RoPE(q), RoPE(k)   theta = rope_theta, rotate-half pairing
                              (lane i with lane i + head_dim/2)
    a = softmax(q k^T / sqrt(head_dim) + causal mask) v
                              (query head j reads KV head j // (H / H_kv))
    h = h + a Wo;  x = RMSNorm(h);  h = h + (SiLU(x Wg) * (x Wu)) Wd

Attention is computed one block of queries at a time against all keys,
a plain softmax with no online rescaling.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.common import F32, rmsnorm

Q_BLOCK = 512


def rope(x, theta: float):
    """x: (S, H, hd) -> rotated, position t at row t."""
    s, _, hd = x.shape
    half = hd // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=F32) * 2.0 / hd)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv_freq[None, :]      # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v, P):
    """q: (S, H, hd); k, v: (S, Hkv, hd) -> (S, H, hd)."""
    s, nh, hd = q.shape
    rep = nh // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)                 # head j <- KV head j // rep
    v = jnp.repeat(v, rep, axis=1)
    qb = min(Q_BLOCK, s)
    kpos = jnp.arange(s)

    def block(t0):
        qt = jax.lax.dynamic_slice_in_dim(q, t0, qb, 0)
        scores = P.mm("thd,shd->hts", qt, k) / jnp.sqrt(jnp.float32(hd))
        causal = (t0 + jnp.arange(qb))[:, None] >= kpos[None, :]
        scores = jnp.where(causal[None], scores, -jnp.inf)
        return P.mm("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(jax.checkpoint(block), jnp.arange(0, s, qb))
    return out.reshape(s, nh, hd)


def layer(lp, h, c: dict, P):
    """One block. lp: this layer's params; h: (S, D) float32."""
    sub = lp["sub0"]
    a, m = sub["attn"], sub["mlp"]
    eps = c["norm_eps"]
    x = rmsnorm(h, sub["ln_mixer"]["scale"], eps)
    q = rope(P.mm("sd,dhk->shk", x, a["wq"]), c["rope_theta"])
    k = rope(P.mm("sd,dhk->shk", x, a["wk"]), c["rope_theta"])
    v = P.mm("sd,dhk->shk", x, a["wv"])
    h = h + P.mm("shk,hkd->sd", _attention(q, k, v, P), a["wo"])
    x = rmsnorm(h, sub["ln_ffn"]["scale"], eps)
    g = jax.nn.silu(P.mm("sd,df->sf", x, m["w_gate"])) * P.mm("sd,df->sf", x, m["w_up"])
    return h + P.mm("sf,fd->sd", g, m["w_down"])


# -- the count of operations that ``chipbench/counts.py`` composes --------
def layers_per_block(c: dict) -> int:
    """Layers of ``n_layers`` that one scanned block spans."""
    return 1


def block_flops(c: dict, s: int):
    """(projection FLOPs, mixer FLOPs, input-projection FLOPs) of one
    block's forward over S tokens. Attention is causal: a query at
    position t reads t + 1 keys, so the score and value products cost
    2 S^2 H hd in all (each half of 4 S^2 H hd)."""
    d, hq, hkv, hd, f = (c["d_model"], c["n_heads"], c["n_kv_heads"],
                         c["head_dim"], c["d_ff"])
    qkv = 2 * d * (hq + 2 * hkv) * hd * s
    out = 2 * hq * hd * d * s
    mlp = 3 * 2 * d * f * s
    attn = 2 * s * s * hq * hd                          # causal QK^T and PV
    return qkv + out + mlp, attn, qkv
