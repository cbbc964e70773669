"""Decoder-only LM covering the dense / MoE / SSM / hybrid / VLM families.

Structure:
  * The layer stack is organized into *blocks* — the scan units — whose
    boundaries are the Hapi split candidates ("for DNNs structured as a
    sequence of blocks we split at block boundary", paper Table 1).
    dense/moe/ssm: block == one layer; gemma2: block == (local, global)
    pair; jamba: block == one 8-sublayer period; nemotron_h: block == one
    period of ``layer_pattern``, one single-branch layer per letter.
  * ``forward_prefix`` / ``forward_suffix`` execute blocks [0, split) and
    [split, N) — the two halves of the paper's tier split. The split is
    static (chosen once per application), so the stacked params are sliced
    statically and each half is an independent scan.
  * Every family exposes the same ``Model`` API consumed by the launcher,
    the COS runtime and the dry-run.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.distributed.autoshard import constrain_act, constrain_logits
from repro.models import layers as L
from repro.models import ssm as S
from repro.models.module import dtype_of, embed_init, remat, slice_stack, stack_init


# ---------------------------------------------------------------------------
# Block plans — static description of the sublayers inside one scan unit
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class SubLayer:
    mixer: str                 # "attn" | "attn_local" | "mamba" | "none"
    ffn: str                   # "mlp" | "moe" | "experts" | "none"


# The letters of ``ModelConfig.layer_pattern``: each layer is one pre-norm
# residual branch with one mixer (M, *) or one expert layer (E).
PATTERN_LAYERS = {
    "M": SubLayer("mamba", "none"),
    "*": SubLayer("attn", "none"),
    "E": SubLayer("none", "experts"),
}


def block_plan(cfg: ModelConfig) -> List[SubLayer]:
    if cfg.layer_pattern:
        return [PATTERN_LAYERS[c] for c in cfg.layer_pattern]
    if cfg.family in ("dense", "vlm"):
        if cfg.local_global_period:
            # gemma2: alternate sliding-window local and global attention.
            return [SubLayer("attn_local", "mlp"), SubLayer("attn", "mlp")]
        return [SubLayer("attn", "mlp")]
    if cfg.family == "moe":
        return [SubLayer("attn", "moe")]
    if cfg.family == "ssm":
        return [SubLayer("mamba", "none")]
    if cfg.family == "hybrid":
        subs = []
        for i in range(cfg.attn_period):
            mixer = "attn" if i == cfg.attn_pos else "mamba"
            ffn = "moe" if (cfg.moe_every and i % cfg.moe_every == 1) else "mlp"
            subs.append(SubLayer(mixer, ffn))
        return subs
    raise ValueError(cfg.family)


# ---------------------------------------------------------------------------
# Sublayer init/apply
# ---------------------------------------------------------------------------
def _sublayer_init(key, cfg: ModelConfig, sub: SubLayer) -> dict:
    dt = dtype_of(cfg.param_dtype)
    keys = jax.random.split(key, 2)
    p: dict = {}
    if sub.mixer in ("attn", "attn_local"):
        p["ln_mixer"] = L.rmsnorm_init(cfg.d_model, dt)
        p["attn"] = L.attention_init(keys[0], cfg)
    elif sub.mixer == "mamba":
        p["ln_mixer"] = L.rmsnorm_init(cfg.d_model, dt)
        p["mamba"] = S.ssm_init(keys[0], cfg)
    if sub.ffn == "mlp":
        p["ln_ffn"] = L.rmsnorm_init(cfg.d_model, dt)
        p["mlp"] = L.mlp_init(keys[1], cfg)
    elif sub.ffn == "moe":
        p["ln_ffn"] = L.rmsnorm_init(cfg.d_model, dt)
        p["moe"] = L.moe_init(keys[1], cfg)
    elif sub.ffn == "experts":
        p["ln_ffn"] = L.rmsnorm_init(cfg.d_model, dt)
        p["experts"] = L.experts_init(keys[1], cfg)
    return p


def _ffn_apply(p, h, cfg: ModelConfig, sub: SubLayer):
    """The sublayer's second residual branch, if it has one."""
    if sub.ffn == "none":
        return h
    x = L.rmsnorm(p["ln_ffn"], h, cfg.norm_eps)
    if sub.ffn == "mlp":
        return h + L.mlp_apply(p["mlp"], x)
    if sub.ffn == "moe":
        return h + L.moe_apply(p["moe"], x, cfg)
    return h + L.expert_layer_apply(p["experts"], x, cfg)


def _sublayer_apply(p, h, cfg: ModelConfig, sub: SubLayer, positions):
    if sub.mixer == "attn":
        h = h + L.attention_apply(
            p["attn"], L.rmsnorm(p["ln_mixer"], h, cfg.norm_eps), cfg,
            positions=positions,
        )
    elif sub.mixer == "attn_local":
        h = h + L.attention_apply(
            p["attn"], L.rmsnorm(p["ln_mixer"], h, cfg.norm_eps), cfg,
            window=cfg.sliding_window, positions=positions,
        )
    elif sub.mixer == "mamba":
        h = h + S.ssm_apply(p["mamba"], L.rmsnorm(p["ln_mixer"], h, cfg.norm_eps), cfg)
    return _ffn_apply(p, h, cfg, sub)


def _sublayer_prefill(p, h, cfg: ModelConfig, sub: SubLayer, positions):
    """Like apply, but also returns the decode cache for this sublayer
    (None for a layer without a mixer)."""
    cache = None
    if sub.mixer in ("attn", "attn_local"):
        x = L.rmsnorm(p["ln_mixer"], h, cfg.norm_eps)
        win = cfg.sliding_window if sub.mixer == "attn_local" else None
        y, cache = _attention_prefill(p["attn"], x, cfg, window=win, positions=positions)
        h = h + y
    elif sub.mixer == "mamba":
        x = L.rmsnorm(p["ln_mixer"], h, cfg.norm_eps)
        y, cache = S.ssm_prefill(p["mamba"], x, cfg)
        h = h + y
    return _ffn_apply(p, h, cfg, sub), cache


def _sublayer_decode(p, h, cache, pos, cfg: ModelConfig, sub: SubLayer):
    if sub.mixer in ("attn", "attn_local"):
        x = L.rmsnorm(p["ln_mixer"], h, cfg.norm_eps)
        win = cfg.sliding_window if sub.mixer == "attn_local" else None
        y, cache = L.attention_decode(p["attn"], x, cache, pos, cfg, window=win)
        h = h + y
    elif sub.mixer == "mamba":
        x = L.rmsnorm(p["ln_mixer"], h, cfg.norm_eps)
        y, cache = S.ssm_decode(p["mamba"], x, cache, cfg)
        h = h + y
    return _ffn_apply(p, h, cfg, sub), cache


def _attention_prefill(params, x, cfg: ModelConfig, *, window, positions):
    """Attention that also emits the (unrepeated) KV cache."""
    y = L.attention_apply(params, x, cfg, window=window, positions=positions)
    # Recompute K/V projections for the cache (XLA CSEs these with the ones
    # inside attention_apply; no duplicate FLOPs in the compiled module).
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if cfg.qkv_bias:
        k = k + params["bk"]
        v = v + params["bv"]
    if cfg.qk_norm:
        k = L.rmsnorm(params["k_norm"], k, cfg.norm_eps)
    if cfg.use_rope:
        k = L.rope(k, positions, cfg.rope_theta)
    return y, L.KVCache(k.astype(jnp.bfloat16), v.astype(jnp.bfloat16))


# ---------------------------------------------------------------------------
# Block init/apply (one scan unit = plan of sublayers)
# ---------------------------------------------------------------------------
def block_init(key, cfg: ModelConfig) -> dict:
    plan = block_plan(cfg)
    keys = jax.random.split(key, len(plan))
    return {f"sub{i}": _sublayer_init(keys[i], cfg, sub) for i, sub in enumerate(plan)}


def block_apply(bp, h, cfg: ModelConfig, positions):
    for i, sub in enumerate(block_plan(cfg)):
        h = _sublayer_apply(bp[f"sub{i}"], h, cfg, sub, positions)
    return constrain_act(h)


def block_prefill(bp, h, cfg: ModelConfig, positions):
    caches = {}
    for i, sub in enumerate(block_plan(cfg)):
        h, caches[f"sub{i}"] = _sublayer_prefill(bp[f"sub{i}"], h, cfg, sub, positions)
    return h, caches


def block_decode(bp, h, cache, pos, cfg: ModelConfig):
    new = {}
    for i, sub in enumerate(block_plan(cfg)):
        h, new[f"sub{i}"] = _sublayer_decode(bp[f"sub{i}"], h, cache[f"sub{i}"], pos, cfg, sub)
    return h, new


def block_init_cache(cfg: ModelConfig, batch: int, smax: int) -> dict:
    out = {}
    for i, sub in enumerate(block_plan(cfg)):
        if sub.mixer in ("attn", "attn_local"):
            out[f"sub{i}"] = L.KVCache(
                k=jnp.zeros((batch, smax, cfg.n_kv_heads, cfg.hdim), jnp.bfloat16),
                v=jnp.zeros((batch, smax, cfg.n_kv_heads, cfg.hdim), jnp.bfloat16),
            )
        elif sub.mixer == "mamba":
            out[f"sub{i}"] = S.ssm_init_cache(cfg, batch)
        else:
            out[f"sub{i}"] = None
    return out


# ---------------------------------------------------------------------------
# The Model API
# ---------------------------------------------------------------------------
class Model(NamedTuple):
    cfg: ModelConfig
    init: Callable[..., Any]
    forward: Callable[..., Any]          # (params, batch) -> logits
    loss: Callable[..., Any]             # (params, batch) -> scalar
    forward_prefix: Callable[..., Any]   # (params, batch, split) -> activations
    forward_suffix: Callable[..., Any]   # (params, acts, batch, split) -> logits
    loss_suffix: Callable[..., Any]      # (trainable, acts, batch, split) -> scalar
    prefill: Callable[..., Any]          # (params, batch) -> (logits, cache)
    decode_step: Callable[..., Any]      # (params, cache, token, pos) -> (logits, cache)
    init_cache: Callable[..., Any]       # (batch, smax) -> cache
    split_params: Callable[..., Any]     # (params, split) -> (frozen, trainable)
    merge_params: Callable[..., Any]     # (frozen, trainable, split) -> params


def _embed_tokens(params, tokens, cfg: ModelConfig, extra_embeds=None):
    h = params["embed"][tokens].astype(dtype_of(cfg.compute_dtype))
    if cfg.family == "vlm" and extra_embeds is not None:
        # LLaVA stub frontend: prepend pre-computed patch embeddings.
        h = jnp.concatenate([extra_embeds.astype(h.dtype), h], axis=1)
    h = h * jnp.sqrt(jnp.float32(cfg.d_model)).astype(h.dtype)
    return constrain_act(h)


def _head(params, h, cfg: ModelConfig):
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    w = params.get("unembed", params.get("embed"))
    logits = jnp.einsum(
        "bsd,vd->bsv", h, w.astype(h.dtype), preferred_element_type=jnp.float32
    )
    if cfg.logit_softcap:
        logits = L._softcap(logits, cfg.logit_softcap)
    # Mask vocab padding.
    if cfg.padded_vocab != cfg.vocab_size:
        pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
        logits = jnp.where(pad_mask[None, None, :], -1e30, logits)
    return constrain_logits(logits)


def cross_entropy(logits, labels, mask=None):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    if mask is None:
        return -ll.mean()
    return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1)


def build_lm(cfg: ModelConfig) -> Model:
    """Decoder LM for families dense/moe/ssm/hybrid/vlm and layer patterns."""
    def init(key):
        k1, k2, k3 = jax.random.split(key, 3)
        dt = dtype_of(cfg.param_dtype)
        params = {
            "embed": embed_init(k1, cfg.padded_vocab, cfg.d_model, dt),
            "blocks": stack_init(
                lambda k, i: block_init(k, cfg), k2, cfg.n_blocks
            ),
            "final_norm": L.rmsnorm_init(cfg.d_model, dt),
        }
        if not cfg.tie_embeddings:
            params["unembed"] = embed_init(k3, cfg.padded_vocab, cfg.d_model, dt)
        return params

    def _scan_blocks(stacked, h, positions):
        body = remat(lambda hh, bp: (block_apply(bp, hh, cfg, positions), None))
        h, _ = jax.lax.scan(body, h, stacked)
        return h

    def _positions(batch):
        tokens = batch["tokens"]
        s = tokens.shape[1]
        if cfg.family == "vlm":
            s = s + cfg.n_patches
        return jnp.arange(s)[None, :]

    def forward(params, batch):
        h = _embed_tokens(params, batch["tokens"], cfg, batch.get("patches"))
        h = _scan_blocks(params["blocks"], h, _positions(batch))
        return _head(params, h, cfg)

    def loss(params, batch):
        logits = forward(params, batch)
        labels = batch["labels"]
        if cfg.family == "vlm":
            logits = logits[:, cfg.n_patches :, :]
        return cross_entropy(logits[:, :-1], labels[:, 1:], batch.get("mask"))

    # ---- Hapi tier split ---------------------------------------------------
    def split_params(params, split: int):
        frozen = {
            "embed": params["embed"],
            "blocks": slice_stack(params["blocks"], 0, split),
        }
        trainable = {
            "blocks": slice_stack(params["blocks"], split, cfg.n_blocks),
            "final_norm": params["final_norm"],
        }
        if not cfg.tie_embeddings:
            trainable["unembed"] = params["unembed"]
        else:
            # Tied embeddings are UNTIED at the TL split: the input embedding
            # stays frozen (feature extraction); the output head becomes a
            # trainable copy — the paper's "train a new classifier" phase.
            # (A copy also keeps buffer donation sound: no aliased leaves
            # across the frozen/trainable trees.)
            trainable["unembed"] = jnp.copy(params["embed"])
        return frozen, trainable

    def merge_params(frozen, trainable, split: int):
        blocks = jax.tree.map(
            lambda a, b: jnp.concatenate([a, b], axis=0),
            frozen["blocks"],
            trainable["blocks"],
        )
        params = {
            "embed": frozen["embed"],
            "blocks": blocks,
            "final_norm": trainable["final_norm"],
            "unembed": trainable["unembed"],
        }
        return params

    def forward_prefix(frozen, batch, split: int):
        h = _embed_tokens(frozen, batch["tokens"], cfg, batch.get("patches"))
        h = _scan_blocks(frozen["blocks"], h, _positions(batch))
        return h

    def _suffix_head_params(trainable):
        return {
            "final_norm": trainable["final_norm"],
            "unembed": trainable["unembed"],
        }

    def forward_suffix(trainable, acts, batch, split: int):
        h = _scan_blocks(trainable["blocks"], acts, _positions(batch))
        return _head(_suffix_head_params(trainable), h, cfg)

    def loss_suffix(trainable, acts, batch, split: int):
        logits = forward_suffix(trainable, acts, batch, split)
        labels = batch["labels"]
        if cfg.family == "vlm":
            logits = logits[:, cfg.n_patches :, :]
        return cross_entropy(logits[:, :-1], labels[:, 1:], batch.get("mask"))

    # ---- serving -------------------------------------------------------------
    def init_cache(batch: int, smax: int):
        one = block_init_cache(cfg, batch, smax)
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (cfg.n_blocks,) + x.shape), one
        )

    def prefill(params, batch):
        h = _embed_tokens(params, batch["tokens"], cfg, batch.get("patches"))
        positions = _positions(batch)

        def body(hh, bp):
            hh, cache = block_prefill(bp, hh, cfg, positions)
            return hh, cache

        h, caches = jax.lax.scan(body, h, params["blocks"])
        logits = _head(params, h[:, -1:, :], cfg)
        return logits, caches

    def decode_step(params, cache, token, pos):
        h = _embed_tokens(params, token, cfg)  # (B,1,D)

        def body(hh, xs):
            bp, cb = xs
            hh, nc = block_decode(bp, hh, cb, pos, cfg)
            return hh, nc

        h, new_cache = jax.lax.scan(body, h, (params["blocks"], cache))
        logits = _head(params, h, cfg)
        return logits, new_cache

    return Model(
        cfg=cfg,
        init=init,
        forward=forward,
        loss=loss,
        forward_prefix=forward_prefix,
        forward_suffix=forward_suffix,
        loss_suffix=loss_suffix,
        prefill=prefill,
        decode_step=decode_step,
        init_cache=init_cache,
        split_params=split_params,
        merge_params=merge_params,
    )
