"""Core NN layers shared by every architecture.

Notable implementation choices (see DESIGN.md §2):
  * Attention has a *chunked, online-softmax* XLA path (``chunked_attention``)
    so that 32k-token prefill never materializes an S x S score matrix —
    this is the pure-XLA twin of the Pallas flash kernel in
    ``repro.kernels.flash_attention`` and keeps the dry-run memory term
    honest. Sliding-window layers slice only the in-window KV blocks, so
    local attention is genuinely sub-quadratic in HLO FLOPs too.
  * The expert layer (nemotron_h) holds a share of the routed experts,
    routes over all of them and drops nothing: the pairs routed to the
    held experts are sorted into one buffer and run through a grouped
    matmul that does no work past each expert's rows.
  * ``moe_apply`` (moonshot, jamba) uses sort/gather dispatch +
    capacity-padded expert buffers + scatter-add combine. Dispatch/combine
    are data movement (zero matmul FLOPs); expert compute is exactly
    ``top_k x capacity_factor`` times the dense-equivalent — the GShard
    one-hot-einsum formulation would inflate HLO FLOPs by >100x and ruin
    the roofline accounting.
  * GQA is implemented by repeating KV heads to the Q-head count *in the
    compute path only*; caches store the unrepeated KV.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.kernels import ops
from repro.models.module import dense_init, dtype_of, ones_init, zeros_init
from repro.obs import device_scope

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm_init(dim: int, dtype) -> dict:
    return {"scale": ones_init((dim,), dtype)}


def rmsnorm(params, x, eps: float = 1e-6):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dt)


def layernorm_init(dim: int, dtype) -> dict:
    return {"scale": ones_init((dim,), dtype), "bias": zeros_init((dim,), dtype)}


def layernorm(params, x, eps: float = 1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * params["scale"] + params["bias"]).astype(dt)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Apply RoPE. x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-jnp.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]  # (..., S, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(
        x.dtype
    )


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def attention_init(key, cfg: ModelConfig) -> dict:
    dt = dtype_of(cfg.param_dtype)
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hdim
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": dense_init(kq, d, (hq, hd), dt),
        "wk": dense_init(kk, d, (hkv, hd), dt),
        "wv": dense_init(kv, d, (hkv, hd), dt),
        "wo": dense_init(ko, hq * hd, (d,), dt).reshape(hq, hd, d),
    }
    if cfg.qkv_bias:
        p["bq"] = zeros_init((hq, hd), dt)
        p["bk"] = zeros_init((hkv, hd), dt)
        p["bv"] = zeros_init((hkv, hd), dt)
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(hd, dt)
        p["k_norm"] = rmsnorm_init(hd, dt)
    return p


def _project_qkv(params, x, cfg: ModelConfig, positions):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(params["k_norm"], k, cfg.norm_eps)
    if cfg.use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def _repeat_kv(k: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(
        b, s, h * n_rep, d
    )


def _softcap(x, cap: Optional[float]):
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


def chunked_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    q_block: int = 512,
    kv_block: int = 512,
) -> jnp.ndarray:
    """Online-softmax attention scanned over (q-block, kv-block) tiles.

    q: (B, S, H, hd) — KV already repeated to H heads. Never materializes
    more than one (q_block, kv_block) score tile per head. For sliding
    window attention only the in-window KV span is sliced per q block, so
    FLOPs scale with S * window instead of S^2.
    """
    b, s, h, hd = q.shape
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    q_block = min(q_block, s)
    kv_block = min(kv_block, s)
    n_q = s // q_block
    assert s % q_block == 0 and s % kv_block == 0, (s, q_block, kv_block)

    # (B, H, S, hd) layout for blocked access.
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    if window is not None and window + q_block < s:
        # Sub-quadratic local path: per q block, slice the KV span
        # [q_start - window, q_start + q_block). span <= s guaranteed.
        span = window + q_block

        def q_step(_, qi):
            q_start = qi * q_block
            qb = jax.lax.dynamic_slice_in_dim(qt, q_start, q_block, axis=2)
            kv_start = jnp.maximum(q_start - window, 0)
            kb = jax.lax.dynamic_slice_in_dim(kt, kv_start, span, axis=2)
            vb = jax.lax.dynamic_slice_in_dim(vt, kv_start, span, axis=2)
            scores = jnp.einsum(
                "bhqd,bhkd->bhqk", qb, kb, preferred_element_type=jnp.float32
            ) * scale
            scores = _softcap(scores, softcap)
            qpos = q_start + jnp.arange(q_block)[:, None]
            kpos = kv_start + jnp.arange(span)[None, :]
            mask = (kpos <= qpos) & (kpos > qpos - window - 1)
            scores = jnp.where(mask, scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(vb.dtype)
            out = jnp.einsum("bhqk,bhkd->bhqd", probs, vb)
            return None, out

        _, blocks = jax.lax.scan(q_step, None, jnp.arange(n_q))
        out = blocks.transpose(1, 2, 0, 3, 4).reshape(b, h, s, hd)
        return out.transpose(0, 2, 1, 3)
    if window is not None:
        window = None  # window covers the whole sequence -> plain causal

    n_kv = s // kv_block

    def q_step(_, qi):
        q_start = qi * q_block
        qb = jax.lax.dynamic_slice_in_dim(qt, q_start, q_block, axis=2)

        def kv_step(carry, ki):
            m, l, acc = carry
            kv_start = ki * kv_block
            kb = jax.lax.dynamic_slice_in_dim(kt, kv_start, kv_block, axis=2)
            vb = jax.lax.dynamic_slice_in_dim(vt, kv_start, kv_block, axis=2)
            scores = jnp.einsum(
                "bhqd,bhkd->bhqk", qb, kb, preferred_element_type=jnp.float32
            ) * scale
            scores = _softcap(scores, softcap)
            if causal:
                qpos = q_start + jnp.arange(q_block)[:, None]
                kpos = kv_start + jnp.arange(kv_block)[None, :]
                scores = jnp.where(kpos <= qpos, scores, -1e30)
            m_new = jnp.maximum(m, scores.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(scores - m_new[..., None])
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(vb.dtype), vb,
                preferred_element_type=jnp.float32,
            )
            return (m_new, l_new, acc_new), None

        init = (
            jnp.full((b, h, q_block), -1e30, jnp.float32),
            jnp.zeros((b, h, q_block), jnp.float32),
            jnp.zeros((b, h, q_block, hd), jnp.float32),
        )
        if causal:
            # Only scan kv blocks that intersect the causal triangle.
            n_kv_needed = (q_start + q_block + kv_block - 1) // kv_block
            # q_start is traced (scan over qi) -> cannot bound statically;
            # scan all blocks but the mask zeroes out future ones. The Pallas
            # kernel (and grid specialization below) recovers the 2x.
            del n_kv_needed
        (m, l, acc), _ = jax.lax.scan(kv_step, init, jnp.arange(n_kv))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.astype(q.dtype)

    _, blocks = jax.lax.scan(q_step, None, jnp.arange(n_q))
    out = blocks.transpose(1, 2, 0, 3, 4).reshape(b, h, s, hd)
    return out.transpose(0, 2, 1, 3)


def attention_apply(
    params,
    x: jnp.ndarray,
    cfg: ModelConfig,
    *,
    window: Optional[int] = None,
    causal: bool = True,
    positions: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Full-sequence attention (train / prefill)."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)[None, :]
    q, k, v = _project_qkv(params, x, cfg, positions)
    n_rep = cfg.n_heads // cfg.n_kv_heads
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    if s <= 1024 and window is None:
        # Small-seq direct path (cheaper HLO for smoke tests).
        scale = 1.0 / jnp.sqrt(jnp.float32(cfg.hdim))
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
        ) * scale
        scores = _softcap(scores, cfg.attn_softcap)
        if causal:
            mask = jnp.tril(jnp.ones((s, s), bool))
            scores = jnp.where(mask, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    else:
        out = chunked_attention(
            q, k, v, causal=causal, window=window, softcap=cfg.attn_softcap
        )
    return jnp.einsum("bshd,hdm->bsm", out, params["wo"])


class KVCache(NamedTuple):
    k: jnp.ndarray  # (B, Smax, Hkv, hd)
    v: jnp.ndarray


def attention_decode(
    params,
    x: jnp.ndarray,              # (B, 1, D)
    cache: KVCache,
    pos: jnp.ndarray,            # scalar int32 — current position
    cfg: ModelConfig,
    *,
    window: Optional[int] = None,
):
    """Single-token decode against a filled KV cache."""
    b = x.shape[0]
    positions = jnp.full((b, 1), pos, dtype=jnp.int32)
    q, k_new, v_new = _project_qkv(params, x, cfg, positions)
    k = jax.lax.dynamic_update_slice_in_dim(cache.k, k_new.astype(cache.k.dtype), pos, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache.v, v_new.astype(cache.v.dtype), pos, axis=1)
    smax = k.shape[1]
    n_rep = cfg.n_heads // cfg.n_kv_heads
    # Grouped score computation without repeating the cache in memory:
    # q: (B, 1, Hkv, n_rep, hd) x k: (B, S, Hkv, hd).
    qg = q.reshape(b, 1, cfg.n_kv_heads, n_rep, cfg.hdim)
    scale = 1.0 / jnp.sqrt(jnp.float32(cfg.hdim))
    scores = jnp.einsum(
        "bqhrd,bshd->bhrqs", qg, k, preferred_element_type=jnp.float32
    ) * scale
    scores = _softcap(scores, cfg.attn_softcap)
    kpos = jnp.arange(smax)
    valid = kpos <= pos
    if window is not None:
        valid &= kpos > pos - window - 1
    scores = jnp.where(valid[None, None, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhrqs,bshd->bqhrd", probs, v)
    out = out.reshape(b, 1, cfg.n_heads, cfg.hdim)
    y = jnp.einsum("bshd,hdm->bsm", out, params["wo"])
    return y, KVCache(k, v)


# ---------------------------------------------------------------------------
# Dense (SwiGLU) MLP
# ---------------------------------------------------------------------------


def mlp_init(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> dict:
    dt = dtype_of(cfg.param_dtype)
    d, f = cfg.d_model, d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, d, (f,), dt),
        "w_up": dense_init(k2, d, (f,), dt),
        "w_down": dense_init(k3, f, (d,), dt),
    }


def mlp_apply(params, x):
    g = jnp.einsum("bsd,df->bsf", x, params["w_gate"])
    u = jnp.einsum("bsd,df->bsf", x, params["w_up"])
    h = jax.nn.silu(g) * u
    return jnp.einsum("bsf,fd->bsd", h, params["w_down"])


# ---------------------------------------------------------------------------
# Mixture of Experts — sort/gather dispatch, capacity buffers, scatter combine
# ---------------------------------------------------------------------------


def moe_init(key, cfg: ModelConfig) -> dict:
    dt = dtype_of(cfg.param_dtype)
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    kr, k1, k2, k3 = jax.random.split(key, 4)
    return {
        "router": dense_init(kr, d, (e,), jnp.float32),
        "w_gate": jax.vmap(lambda k: dense_init(k, d, (f,), dt))(jax.random.split(k1, e)),
        "w_up": jax.vmap(lambda k: dense_init(k, d, (f,), dt))(jax.random.split(k2, e)),
        "w_down": jax.vmap(lambda k: dense_init(k, f, (d,), dt))(jax.random.split(k3, e)),
    }


def moe_apply(params, x: jnp.ndarray, cfg: ModelConfig):
    """Top-k MoE over tokens of one group. x: (B, S, D) -> (B, S, D).

    Groups are the batch rows (dispatch never crosses rows), which keeps the
    dispatch tensors small and lets XLA shard groups over the data axis and
    experts over the model axis.
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = int(cfg.capacity_factor * s * k / e + 1)
    cap = min(cap, s)

    gate_logits = jnp.einsum(
        "bsd,de->bse", x.astype(jnp.float32), params["router"]
    )
    probs = jax.nn.softmax(gate_logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)              # (B, S, K)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    flat_e = top_e.reshape(b, s * k)                    # slot -> expert
    flat_p = top_p.reshape(b, s * k)
    slot_tok = jnp.tile(jnp.arange(s)[:, None], (1, k)).reshape(s * k)

    # Sort slots by expert (stable: ties keep token order).
    sort_idx = jnp.argsort(flat_e, axis=-1, stable=True)          # (B, S*K)
    sorted_e = jnp.take_along_axis(flat_e, sort_idx, axis=-1)
    sorted_tok = slot_tok[sort_idx]                                # (B, S*K)
    counts = jax.nn.one_hot(flat_e, e, dtype=jnp.int32).sum(axis=1)  # (B, E)
    offsets = jnp.cumsum(counts, axis=-1) - counts                 # exclusive

    # Buffer index table: token feeding buffer slot (expert, c).
    grid_c = jnp.arange(cap)[None, None, :]                        # (1,1,C)
    gather_pos = offsets[:, :, None] + grid_c                      # (B,E,C)
    valid = grid_c < counts[:, :, None]                            # (B,E,C)
    gather_pos = jnp.clip(gather_pos, 0, s * k - 1)
    buf_tok = jax.vmap(lambda st, gp: st[gp])(sorted_tok, gather_pos)  # (B,E,C)

    # Dispatch (gather — no FLOPs). Without explicit constraints XLA SPMD
    # replicates the expert buffers over the data axis (a 100+ GiB/step
    # all-gather+all-reduce at moonshot scale — see EXPERIMENTS.md §Perf I3).
    from repro.distributed.autoshard import constrain_dims

    xb = jax.vmap(lambda xx, bt: xx[bt])(x, buf_tok)               # (B,E,C,D)
    xb = jnp.where(valid[..., None], xb, 0)
    xb = constrain_dims(xb, ("batch", "model", None, None),
                        alt=("batch", None, None, None))

    # Expert FFN (batched over E).
    g = jnp.einsum("becd,edf->becf", xb, params["w_gate"])
    u = jnp.einsum("becd,edf->becf", xb, params["w_up"])
    h = jax.nn.silu(g) * u
    h = constrain_dims(h, ("batch", "model", None, None),
                       alt=("batch", None, None, "model"))
    yb = jnp.einsum("becf,efd->becd", h, params["w_down"])         # (B,E,C,D)
    yb = constrain_dims(yb, ("batch", "model", None, None),
                        alt=("batch", None, None, None))

    # Combine: scatter-add expert outputs back to token positions, weighted.
    sorted_p = jnp.take_along_axis(flat_p, sort_idx, axis=-1)
    buf_w = jax.vmap(lambda sp, gp: sp[gp])(sorted_p, gather_pos)  # (B,E,C)
    contrib = (yb * buf_w[..., None]).astype(jnp.float32)
    contrib = jnp.where(valid[..., None], contrib, 0)

    flat_contrib = contrib.reshape(b, e * cap, d)
    flat_tok = buf_tok.reshape(b, e * cap)
    y = jnp.zeros((b, s, d), jnp.float32)
    y = jax.vmap(lambda yy, tt, cc: yy.at[tt].add(cc))(y, flat_tok, flat_contrib)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Expert layer (nemotron_h) — a held share of the routed experts, dropless
# ---------------------------------------------------------------------------


def experts_init(key, cfg: ModelConfig) -> dict:
    """The router over all ``n_experts`` routed experts, the bias that
    shifts their selection, the ``n_held`` relu^2 experts this chip
    holds (ids [0, n_held)) and the shared expert. Weights are laid out
    fan-in first, as ``dense_init`` lays out every weight: ``w_up``
    (D, E_held, F), ``w_down`` (F, E_held, D)."""
    dt = dtype_of(cfg.param_dtype)
    d, f, fs, eh = cfg.d_model, cfg.d_ff, cfg.shared_expert_ff, cfg.n_held
    kr, k1, k2, k3, k4 = jax.random.split(key, 5)
    return {
        "router": dense_init(kr, d, (cfg.n_experts,), jnp.float32),
        "e_score_correction_bias": zeros_init((cfg.n_experts,), jnp.float32),
        "w_up": dense_init(k1, d, (eh, f), dt),
        "w_down": dense_init(k2, f, (eh, d), dt),
        "shared": {"w_up": dense_init(k3, d, (fs,), dt),
                   "w_down": dense_init(k4, fs, (d,), dt)},
    }


def route(params, x32, cfg: ModelConfig):
    """Top-k routed experts of each token and their gate weights, over
    all ``n_experts``: the sigmoid router in float32 chooses on the
    scores plus ``e_score_correction_bias`` (which only selects: it
    carries no gradient); the gate weights are the chosen scores,
    normalized over the k, times ``routed_scaling``.
    x32: (T, D) -> ids (T, k) int32, weights (T, k)."""
    logits = jnp.dot(x32, params["router"], precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    choice = scores + jax.lax.stop_gradient(params["e_score_correction_bias"])
    _, ids = jax.lax.top_k(choice, cfg.top_k)
    w = jnp.take_along_axis(scores, ids, axis=-1)
    return ids, w / (w.sum(-1, keepdims=True) + 1e-20) * cfg.routed_scaling


def relu2_mlp(params, x):
    """down(relu(x up)^2), no gate: one relu^2 expert."""
    u = jnp.einsum("...d,df->...f", x, params["w_up"])
    return jnp.einsum("...f,fd->...d", jnp.square(jax.nn.relu(u)), params["w_down"])


def sort_pairs(ids, held: int, first: int = 0):
    """The (token, slot) pairs of ``ids`` (T, k) sorted by held expert
    (ids ``first`` onward, ``held`` of them): ``order`` (T*k,), the pair
    in each sorted position (pair t*k + j is token t's slot j); ``pos``
    (T, k), each pair's position, the inverse of ``order`` (a second
    argsort, of integers); ``sizes`` (held,), each expert's
    pairs. Pairs routed to experts held elsewhere sort after every
    group, at positions >= ``sizes.sum()``."""
    local = ids.reshape(-1) - first
    local = jnp.where((local >= 0) & (local < held), local, held)      # not held: last
    order = jnp.argsort(local, stable=True)
    pos = jnp.argsort(order)
    sizes = jax.ops.segment_sum(jnp.ones_like(local), local, held)    # drops "not held"
    return order, pos.reshape(ids.shape), sizes


def _slot_rows(rows, pos, n):
    """For each slot j: whether each token's pair has a row (pos < n),
    (T, 1), and the row ``rows[pos[:, j]]`` in float32, (T, D); one row
    gather a slot. A pair without a row reads row 0, to be selected away
    with ``jnp.where``, never multiplied by a mask: rows past every group
    may be undefined."""
    held = pos < n
    at = jnp.where(held, pos, 0)
    return [(held[:, j, None], rows[at[:, j]].astype(jnp.float32))
            for j in range(pos.shape[1])]


@jax.custom_vjp
def dispatch(xt, tok, pos, n):
    """The sorted buffer: row r holds ``xt[tok[r]]`` for r < n, else 0
    (rows past ``n`` gather a zero row appended to ``xt``, so no pass
    over the buffer selects). Its transpose gathers each token's k rows
    back through ``pos`` and sums them in float32: no scatter over rows."""
    padded = jnp.concatenate([xt, jnp.zeros((1, xt.shape[1]), xt.dtype)])
    return padded[jnp.where(jnp.arange(tok.shape[0]) < n, tok, xt.shape[0])]


def _dispatch_fwd(xt, tok, pos, n):
    return dispatch(xt, tok, pos, n), (pos, n)


def _dispatch_bwd(res, dxb):
    pos, n = res
    dxt = sum(jnp.where(h, r, 0.0) for h, r in _slot_rows(dxb, pos, n))
    return dxt.astype(dxb.dtype), None, None, None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine(yb, gate, order, pos, n):
    """float32 (T, D): each token's rows of ``yb`` (through ``pos``),
    weighted by its gate weights (T, k), token-major. Its transpose is
    a row gather too: d yb[r] = gate of pair ``order[r]`` times d y of
    its token, and d gate[t, j] = <d y[t], yb[pos[t, j]]>."""
    return sum(jnp.where(h, r * gate[:, j, None], 0.0)
               for j, (h, r) in enumerate(_slot_rows(yb, pos, n)))


def _combine_fwd(yb, gate, order, pos, n):
    return combine(yb, gate, order, pos, n), (yb, gate, order, pos, n)


def _combine_bwd(res, dy):
    yb, gate, order, pos, n = res
    k = gate.shape[1]
    rows = (jnp.arange(order.shape[0]) < n)[:, None]
    w = gate.reshape(-1)[order][:, None]
    dyb = jnp.where(rows, w * dy[order // k], 0).astype(yb.dtype)
    dgate = jnp.concatenate([jnp.where(h, jnp.sum(dy * r, -1, keepdims=True), 0.0)
                             for h, r in _slot_rows(yb, pos, n)], axis=1)
    return dyb, dgate.astype(gate.dtype), None, None, None


combine.defvjp(_combine_fwd, _combine_bwd)


def routed_experts(params, x: jnp.ndarray, cfg: ModelConfig, first: int = 0):
    """The routed experts' part of the layer, for the experts held in
    ``params`` (ids ``first`` onward). x: (B, S, D) -> float32 (B, S, D).

    The (token, slot) pairs routed to held experts are sorted by expert
    into a buffer of B*S*min(k, E_held) rows, the most they can fill, so
    nothing is dropped; the grouped matmuls do no work past each
    expert's rows. Dispatch and combine are row gathers, each the
    other's transpose, forward and backward: each token gathers its k
    rows back and sums them, weighted, in float32. Pairs routed to
    experts held elsewhere contribute nothing."""
    b, s, d = x.shape
    k, held = cfg.top_k, params["w_up"].shape[1]
    t, m = b * s, b * s * min(cfg.top_k, held)
    xt = x.reshape(t, d)
    with device_scope("moe.route"):
        ids, gate = route(params, xt.astype(jnp.float32), cfg)
        order, pos, sizes = sort_pairs(ids, held, first)
        order, n = order[:m], sizes.sum()
        xb = dispatch(xt, order // k, pos, n)
    with device_scope("moe.experts"):
        up = ops.grouped_matmul(xb, jnp.moveaxis(params["w_up"], 1, 0), sizes)
        yb = ops.grouped_matmul(jnp.square(jax.nn.relu(up)),
                                jnp.moveaxis(params["w_down"], 1, 0), sizes)
    with device_scope("moe.route"):
        # Rows past every group are undefined (the kernel leaves them
        # unwritten): the combine selects rows, so they meet no weight.
        y = combine(yb, gate, order, pos, n)
    return y.reshape(b, s, d)


def expert_layer_apply(params, x: jnp.ndarray, cfg: ModelConfig):
    """The expert layer: the held routed experts plus the shared expert.
    x: (B, S, D) -> (B, S, D)."""
    y = routed_experts(params, x, cfg)
    with device_scope("moe.shared"):
        y = y + relu2_mlp(params["shared"], x).astype(jnp.float32)
    return y.astype(x.dtype)


def moe_aux_loss(params, x: jnp.ndarray, cfg: ModelConfig):
    """Load-balancing auxiliary loss (Switch-style)."""
    gate_logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(gate_logits, axis=-1)
    top1 = jnp.argmax(probs, axis=-1)
    frac_tokens = jax.nn.one_hot(top1, cfg.n_experts).mean(axis=(0, 1))
    frac_probs = probs.mean(axis=(0, 1))
    return cfg.n_experts * jnp.sum(frac_tokens * frac_probs)


# ---------------------------------------------------------------------------
# Depthwise causal conv (mamba frontend)
# ---------------------------------------------------------------------------


def causal_conv1d(w: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Depthwise causal conv. w: (W, C), x: (B, S, C)."""
    width = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(width):
        out = out + xp[:, i : i + x.shape[1], :].astype(jnp.float32) * w[i][None, None, :]
    return out.astype(x.dtype)
