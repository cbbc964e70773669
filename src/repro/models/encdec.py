"""Whisper-style encoder–decoder backbone.

The conv/mel frontend is a STUB per the assignment: ``input_specs()``
provides pre-computed frame embeddings of shape (B, S_frames, d_model).

Hapi mapping: the TL feature-extraction prefix is the
*encoder* — its blocks are the split candidates; the trainable part is the
remaining encoder blocks + the decoder. Decode shapes exercise the decoder
with a self-attention KV cache of ``seq_len`` plus a cross-attention cache
over a fixed 1500-frame encoder output (whisper's 30 s window).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.distributed.autoshard import constrain_act, constrain_logits
from repro.models import layers as L
from repro.models.module import dtype_of, embed_init, remat, slice_stack, stack_init
from repro.models.transformer import Model, cross_entropy

CROSS_ATTN_FRAMES = 1500  # whisper 30s window


# ---------------------------------------------------------------------------
# Cross attention
# ---------------------------------------------------------------------------
def cross_attention_apply(params, x, enc_kv, cfg: ModelConfig):
    """x: (B, S_dec, D) attends over enc K/V: (B, S_enc, H, hd)."""
    k, v = enc_kv
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    n_rep = cfg.n_heads // cfg.n_kv_heads
    k = L._repeat_kv(k.astype(q.dtype), n_rep)
    v = L._repeat_kv(v.astype(q.dtype), n_rep)
    scale = 1.0 / jnp.sqrt(jnp.float32(cfg.hdim))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    return jnp.einsum("bshd,hdm->bsm", out, params["wo"])


def cross_kv(params, enc_out, cfg: ModelConfig):
    k = jnp.einsum("bsd,dhk->bshk", enc_out, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out, params["wv"])
    return k, v


# ---------------------------------------------------------------------------
# Encoder / decoder blocks
# ---------------------------------------------------------------------------
def enc_block_init(key, cfg: ModelConfig) -> dict:
    dt = dtype_of(cfg.param_dtype)
    k1, k2 = jax.random.split(key)
    return {
        "ln1": L.layernorm_init(cfg.d_model, dt),
        "attn": L.attention_init(k1, cfg),
        "ln2": L.layernorm_init(cfg.d_model, dt),
        "mlp": L.mlp_init(k2, cfg),
    }


def enc_block_apply(bp, h, cfg: ModelConfig, positions):
    h = h + L.attention_apply(
        bp["attn"], L.layernorm(bp["ln1"], h, cfg.norm_eps), cfg,
        causal=False, positions=positions,
    )
    h = h + L.mlp_apply(bp["mlp"], L.layernorm(bp["ln2"], h, cfg.norm_eps))
    return constrain_act(h)


def dec_block_init(key, cfg: ModelConfig) -> dict:
    dt = dtype_of(cfg.param_dtype)
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "ln1": L.layernorm_init(cfg.d_model, dt),
        "self_attn": L.attention_init(k1, cfg),
        "ln2": L.layernorm_init(cfg.d_model, dt),
        "cross_attn": L.attention_init(k2, cfg),
        "ln3": L.layernorm_init(cfg.d_model, dt),
        "mlp": L.mlp_init(k3, cfg),
    }


def dec_block_apply(bp, h, enc_kv, cfg: ModelConfig, positions):
    h = h + L.attention_apply(
        bp["self_attn"], L.layernorm(bp["ln1"], h, cfg.norm_eps), cfg,
        positions=positions,
    )
    h = h + cross_attention_apply(
        bp["cross_attn"], L.layernorm(bp["ln2"], h, cfg.norm_eps), enc_kv, cfg
    )
    h = h + L.mlp_apply(bp["mlp"], L.layernorm(bp["ln3"], h, cfg.norm_eps))
    return constrain_act(h)


def dec_block_decode(bp, h, self_cache, enc_kv, pos, cfg: ModelConfig):
    x = L.layernorm(bp["ln1"], h, cfg.norm_eps)
    y, self_cache = L.attention_decode(bp["self_attn"], x, self_cache, pos, cfg)
    h = h + y
    h = h + cross_attention_apply(
        bp["cross_attn"], L.layernorm(bp["ln2"], h, cfg.norm_eps), enc_kv, cfg
    )
    h = h + L.mlp_apply(bp["mlp"], L.layernorm(bp["ln3"], h, cfg.norm_eps))
    return h, self_cache


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------
def build_encdec(cfg: ModelConfig) -> Model:
    def init(key):
        k1, k2, k3, k4 = jax.random.split(key, 4)
        dt = dtype_of(cfg.param_dtype)
        return {
            "enc_blocks": stack_init(lambda k, i: enc_block_init(k, cfg), k1, cfg.n_enc_layers),
            "enc_norm": L.layernorm_init(cfg.d_model, dt),
            "dec_embed": embed_init(k2, cfg.padded_vocab, cfg.d_model, dt),
            "dec_pos": embed_init(k3, 65536, cfg.d_model, dt),
            "dec_blocks": stack_init(lambda k, i: dec_block_init(k, cfg), k4, cfg.n_dec_layers),
            "dec_norm": L.layernorm_init(cfg.d_model, dt),
        }

    def _encode_from(blocks, h, positions):
        body = remat(lambda hh, bp: (enc_block_apply(bp, hh, cfg, positions), None))
        h, _ = jax.lax.scan(body, h, blocks)
        return h

    def _decode_full(params, enc_out, tokens):
        s = tokens.shape[1]
        h = params["dec_embed"][tokens].astype(dtype_of(cfg.compute_dtype))
        h = constrain_act(h + params["dec_pos"][:s][None].astype(h.dtype))
        positions = jnp.arange(s)[None, :]

        def body(hh, bp):
            kv = cross_kv(bp["cross_attn"], enc_out, cfg)
            return dec_block_apply(bp, hh, kv, cfg, positions), None

        body = remat(body)
        h, _ = jax.lax.scan(body, h, params["dec_blocks"])
        h = L.layernorm(params["dec_norm"], h, cfg.norm_eps)
        logits = jnp.einsum(
            "bsd,vd->bsv", h, params["dec_embed"].astype(h.dtype),
            preferred_element_type=jnp.float32,
        )
        if cfg.padded_vocab != cfg.vocab_size:
            pad = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
            logits = jnp.where(pad[None, None, :], -1e30, logits)
        return constrain_logits(logits)

    def forward(params, batch):
        frames = constrain_act(batch["frames"].astype(dtype_of(cfg.compute_dtype)))
        positions = jnp.arange(frames.shape[1])[None, :]
        enc = _encode_from(params["enc_blocks"], frames, positions)
        enc = L.layernorm(params["enc_norm"], enc, cfg.norm_eps)
        return _decode_full(params, enc, batch["tokens"])

    def loss(params, batch):
        logits = forward(params, batch)
        return cross_entropy(logits[:, :-1], batch["labels"][:, 1:])

    # ---- Hapi split: prefix = first `split` encoder blocks -----------------
    def split_params(params, split: int):
        frozen = {"enc_blocks": slice_stack(params["enc_blocks"], 0, split)}
        trainable = dict(params)
        trainable["enc_blocks"] = slice_stack(params["enc_blocks"], split, cfg.n_enc_layers)
        return frozen, trainable

    def merge_params(frozen, trainable, split: int):
        params = dict(trainable)
        params["enc_blocks"] = jax.tree.map(
            lambda a, b: jnp.concatenate([a, b], axis=0),
            frozen["enc_blocks"],
            trainable["enc_blocks"],
        )
        return params

    def forward_prefix(frozen, batch, split: int):
        frames = batch["frames"].astype(dtype_of(cfg.compute_dtype))
        positions = jnp.arange(frames.shape[1])[None, :]
        return _encode_from(frozen["enc_blocks"], frames, positions)

    def forward_suffix(trainable, acts, batch, split: int):
        positions = jnp.arange(acts.shape[1])[None, :]
        enc = _encode_from(trainable["enc_blocks"], acts, positions)
        enc = L.layernorm(trainable["enc_norm"], enc, cfg.norm_eps)
        return _decode_full(trainable, enc, batch["tokens"])

    def loss_suffix(trainable, acts, batch, split: int):
        logits = forward_suffix(trainable, acts, batch, split)
        return cross_entropy(logits[:, :-1], batch["labels"][:, 1:])

    # ---- serving -------------------------------------------------------------
    def init_cache(batch: int, smax: int):
        kv = lambda s: L.KVCache(
            k=jnp.zeros((cfg.n_dec_layers, batch, s, cfg.n_kv_heads, cfg.hdim), jnp.bfloat16),
            v=jnp.zeros((cfg.n_dec_layers, batch, s, cfg.n_kv_heads, cfg.hdim), jnp.bfloat16),
        )
        return {"self": kv(smax), "cross": kv(CROSS_ATTN_FRAMES)}

    def prefill(params, batch):
        frames = batch["frames"].astype(dtype_of(cfg.compute_dtype))
        positions = jnp.arange(frames.shape[1])[None, :]
        enc = _encode_from(params["enc_blocks"], frames, positions)
        enc = L.layernorm(params["enc_norm"], enc, cfg.norm_eps)
        enc_c = enc[:, : min(CROSS_ATTN_FRAMES, enc.shape[1])]

        tokens = batch["tokens"]
        b, s = tokens.shape
        smax = batch.get("smax", s + 64)
        h = params["dec_embed"][tokens].astype(dtype_of(cfg.compute_dtype))
        h = h + params["dec_pos"][:s][None].astype(h.dtype)
        tok_pos = jnp.arange(s)[None, :]

        def body(hh, bp):
            kv = cross_kv(bp["cross_attn"], enc_c, cfg)
            x = L.layernorm(bp["ln1"], hh, cfg.norm_eps)
            k = jnp.einsum("bsd,dhk->bshk", x, bp["self_attn"]["wk"])
            v = jnp.einsum("bsd,dhk->bshk", x, bp["self_attn"]["wv"])
            k = L.rope(k, tok_pos, cfg.rope_theta)
            hh = dec_block_apply(bp, hh, kv, cfg, tok_pos)
            pad = lambda a: jnp.pad(
                a.astype(jnp.bfloat16), ((0, 0), (0, smax - s), (0, 0), (0, 0))
            )
            return hh, (
                L.KVCache(pad(k), pad(v)),
                L.KVCache(kv[0].astype(jnp.bfloat16), kv[1].astype(jnp.bfloat16)),
            )

        h, (self_c, cross_c) = jax.lax.scan(body, h, params["dec_blocks"])
        h = L.layernorm(params["dec_norm"], h, cfg.norm_eps)
        logits = jnp.einsum(
            "bsd,vd->bsv", h[:, -1:, :], params["dec_embed"].astype(h.dtype),
            preferred_element_type=jnp.float32,
        )
        return logits, {"self": self_c, "cross": cross_c}

    def decode_step(params, cache, token, pos):
        h = params["dec_embed"][token].astype(dtype_of(cfg.compute_dtype))
        pos_emb = jax.lax.dynamic_slice_in_dim(params["dec_pos"], pos, 1, axis=0)
        h = h + pos_emb[None, 0].astype(h.dtype)

        def body(hh, xs):
            bp, self_c, cross_c = xs
            hh, self_c = dec_block_decode(bp, hh, self_c, (cross_c.k, cross_c.v), pos, cfg)
            return hh, self_c

        h, new_self = jax.lax.scan(
            body, h, (params["dec_blocks"], cache["self"], cache["cross"])
        )
        h = L.layernorm(params["dec_norm"], h, cfg.norm_eps)
        logits = jnp.einsum(
            "bsd,vd->bsv", h, params["dec_embed"].astype(h.dtype),
            preferred_element_type=jnp.float32,
        )
        return logits, {"self": new_self, "cross": cache["cross"]}

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
