"""Per-layer profiling (paper §5.3 / §3 measurement study).

The paper combines statically-known sizes with a one-sample profiling run
because PyTorch's allocator is unpredictable. Under XLA the static story is
exact: ``jax.eval_shape`` gives every boundary activation without
allocating a byte, and ``compiled.memory_analysis()`` gives the true peak.
We keep the paper's *over-estimation discipline*: every memory estimate is
inflated by ``headroom`` so adaptation never under-provisions (OOM-safe).

Two entry points:
  * ``profile_lm``      — block-boundary profile for the assigned LM archs.
  * ``profile_layered`` — exact per-layer profile for the paper's vision
                           models (Figs. 2–4 reproduction).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.models.module import dtype_of, tree_bytes
from repro.models.transformer import SubLayer, block_plan


@dataclass
class LayerProfile:
    """Per split-boundary profile. Index i = state after block/layer i-1,
    i in [0, n]; i = 0 is the raw input (no pushdown)."""
    name: str
    n_boundaries: int                      # == n_blocks + 1
    input_bytes: float                     # app input, per sample
    out_bytes: List[float]                 # boundary activation bytes / sample
    cum_flops: List[float]                 # prefix FLOPs / sample up to boundary
    act_peak_bytes: List[float]            # fwd working set / sample up to boundary
    prefix_param_bytes: List[float]        # param bytes of blocks [0, i)
    model_param_bytes: float
    freeze_index: int
    headroom: float = 0.08

    @property
    def total_flops(self) -> float:
        return self.cum_flops[-1]

    def memory_estimate(self, boundary: int, batch: int) -> float:
        """OOM-safe estimate of running the prefix [0, boundary) with
        ``batch`` samples (paper §5.3: model + batch-proportional part,
        over-estimated by headroom)."""
        m = self.prefix_param_bytes[boundary] + batch * self.act_peak_bytes[boundary]
        return m * (1.0 + self.headroom)

    def suffix_memory_estimate(self, boundary: int, batch: int, train: bool) -> float:
        act = self.act_peak_bytes[-1] - (
            self.act_peak_bytes[boundary] - self.out_bytes[boundary]
        )
        params = self.model_param_bytes - self.prefix_param_bytes[boundary]
        mult = 3.0 if train else 1.0      # grads + optimizer residency
        return (params * mult + batch * act) * (1.0 + self.headroom)


# ---------------------------------------------------------------------------
# Analytic FLOPs for LM sublayers (per sample of seq length S)
# ---------------------------------------------------------------------------
def _attn_flops(cfg: ModelConfig, s: int, window: Optional[int]) -> float:
    hd, hq, hkv, d = cfg.hdim, cfg.n_heads, cfg.n_kv_heads, cfg.d_model
    proj = 2 * s * d * (hq + 2 * hkv) * hd + 2 * s * hq * hd * d
    kv_span = min(window + 512, s) if window else s
    scores = 2 * s * kv_span * hq * hd * 2          # QK^T and PV
    return proj + scores


def _mlp_flops(cfg: ModelConfig, s: int) -> float:
    return 2 * s * 3 * cfg.d_model * cfg.d_ff


def _moe_flops(cfg: ModelConfig, s: int) -> float:
    router = 2 * s * cfg.d_model * cfg.n_experts
    expert = 2 * s * cfg.top_k * cfg.capacity_factor * 3 * cfg.d_model * cfg.d_ff
    return router + expert


def _experts_flops(cfg: ModelConfig, s: int) -> float:
    """The expert layer under balanced routing: the router over all routed
    experts, top_k x E_held / E_routed relu^2 experts (up and down) a
    token on the experts this chip holds, and the shared expert."""
    d = cfg.d_model
    router = 2 * s * d * cfg.n_experts
    routed = 2 * s * cfg.top_k * cfg.n_held / cfg.n_experts * 2 * d * cfg.d_ff
    shared = 2 * s * 2 * d * cfg.shared_expert_ff
    return router + routed + shared


def _ssm_flops(cfg: ModelConfig, s: int) -> float:
    d, di, h, p = cfg.d_model, cfg.d_inner, cfg.ssm_nheads, cfg.ssm_headdim
    n = cfg.ssm_state
    gn = cfg.ssm_groups * n
    q = min(cfg.ssm_chunk, s)
    proj = 2 * s * d * (2 * di + 2 * gn + h) + 2 * s * di * d
    conv = 2 * s * cfg.conv_width * (di + 2 * gn)
    # chunked SSD: CB scores (Q*N), diag (Q*H*P... dominated by Q terms),
    # state in/out (N*P*H) per token.
    ssd = 2 * s * (q * n + q * h + q * h * p) + 4 * s * n * p * h
    return proj + conv + ssd


def sublayer_flops(cfg: ModelConfig, sub: SubLayer, s: int) -> float:
    f = 0.0
    if sub.mixer == "attn":
        f = _attn_flops(cfg, s, None)
    elif sub.mixer == "attn_local":
        f = _attn_flops(cfg, s, cfg.sliding_window)
    elif sub.mixer == "mamba":
        f = _ssm_flops(cfg, s)
    if sub.ffn == "mlp":
        f += _mlp_flops(cfg, s)
    elif sub.ffn == "moe":
        f += _moe_flops(cfg, s)
    elif sub.ffn == "experts":
        f += _experts_flops(cfg, s)
    return f


def block_flops(cfg: ModelConfig, s: int) -> float:
    if cfg.family == "encdec":
        # Encoder block: bidirectional self-attn + MLP over the frames.
        return sublayer_flops(cfg, SubLayer("attn", "mlp"), s)
    return sum(sublayer_flops(cfg, sub, s) for sub in block_plan(cfg))


def encdec_decoder_flops(cfg: ModelConfig, s_enc: int) -> float:
    """Decoder stack: causal self-attn over dec_seq + cross-attn over the
    encoder output + MLP, per sample."""
    sd = cfg.dec_seq
    hd, hq, hkv, d = cfg.hdim, cfg.n_heads, cfg.n_kv_heads, cfg.d_model
    self_attn = _attn_flops(cfg, sd, None)
    cross_proj = 2 * sd * d * hq * hd + 2 * s_enc * d * 2 * hkv * hd + 2 * sd * hq * hd * d
    cross_scores = 2 * sd * min(s_enc, 1500) * hq * hd * 2
    mlp = _mlp_flops(cfg, sd)
    return cfg.n_dec_layers * (self_attn + cross_proj + cross_scores + mlp)


def embed_flops(cfg: ModelConfig, s: int) -> float:
    return 0.0  # gather


def head_flops(cfg: ModelConfig, s: int) -> float:
    return 2 * s * cfg.d_model * cfg.padded_vocab


# ---------------------------------------------------------------------------
# LM profile
# ---------------------------------------------------------------------------
def profile_lm(cfg: ModelConfig, seq_len: int, headroom: float = 0.08) -> LayerProfile:
    act_dt = jnp.dtype(dtype_of(cfg.compute_dtype)).itemsize
    par_dt = jnp.dtype(dtype_of(cfg.param_dtype)).itemsize
    s = seq_len
    d = cfg.d_model

    if cfg.family == "vlm":
        input_bytes = (s - cfg.n_patches) * 4 + cfg.n_patches * d * act_dt
    elif cfg.family == "encdec":
        input_bytes = s * d * act_dt + cfg.dec_seq * 4
    else:
        input_bytes = s * 4  # int32 tokens

    boundary_act = s * d * act_dt          # (S, D) hidden state per sample
    n = cfg.n_blocks
    bp = cfg.block_params() * par_dt
    bf = block_flops(cfg, s)

    # Working set of the scanned prefix per sample: input + output of the
    # live block plus attention/moe workspace (~4x hidden) — constant in
    # depth thanks to scan. Embedding output included from boundary 1 on.
    work = 6 * boundary_act

    out_bytes = [float(input_bytes)] + [float(boundary_act)] * n
    cum_flops = [0.0]
    act_peak = [float(input_bytes)]
    prefix_pb = [0.0]
    emb_bytes = cfg.padded_vocab * d * par_dt
    for i in range(1, n + 1):
        cum_flops.append(embed_flops(cfg, s) + i * bf)
        act_peak.append(float(work))
        prefix_pb.append(emb_bytes + i * bp)
    if cfg.family == "encdec":
        cum_flops[-1] += encdec_decoder_flops(cfg, s) + 2 * cfg.dec_seq * d * cfg.padded_vocab
    else:
        cum_flops[-1] += head_flops(cfg, s)

    return LayerProfile(
        name=cfg.name,
        n_boundaries=n + 1,
        input_bytes=float(input_bytes),
        out_bytes=out_bytes,
        cum_flops=cum_flops,
        act_peak_bytes=act_peak,
        prefix_param_bytes=prefix_pb,
        model_param_bytes=cfg.param_count() * par_dt,
        freeze_index=cfg.freeze_index,
        headroom=headroom,
    )


# ---------------------------------------------------------------------------
# Vision-model profile (exact, via eval_shape — the paper's profiling run)
# ---------------------------------------------------------------------------
def profile_layered(vm, headroom: float = 0.08) -> LayerProfile:
    """Exact per-layer profile of a VisionModel with a single synthetic
    sample (paper §5.3: 'a single data sample is sufficient')."""
    key = jax.random.PRNGKey(0)
    params = vm.init(key)
    x_spec = jax.ShapeDtypeStruct((1,) + vm.input_shape, jnp.float32)

    out_bytes = [float(np.prod(vm.input_shape)) * 4]
    act_peak = [out_bytes[0]]
    cum_flops = [0.0]
    prefix_pb = [0.0]

    spec = x_spec
    running_pb = 0.0
    running_flops = 0.0
    for i, name in enumerate(vm.layer_names):
        nxt = jax.eval_shape(lambda p, a: vm.apply_range(p, a, i, i + 1), params, spec)
        layer_bytes = float(np.prod(nxt.shape) * nxt.dtype.itemsize)
        p_bytes = tree_bytes(params[i])
        # FLOPs: dominated by matmul/conv layers — estimate 2 * weight-size
        # * spatial positions for convs, 2 * weight-size for fc.
        flops = _layer_flops_estimate(params[i], spec, nxt)
        running_pb += p_bytes
        running_flops += flops
        out_bytes.append(layer_bytes)
        cur = float(np.prod(spec.shape) * 4 + layer_bytes)
        act_peak.append(max(act_peak[-1], cur))  # prefix working-set peak
        cum_flops.append(running_flops)
        prefix_pb.append(running_pb)
        spec = nxt

    return LayerProfile(
        name=vm.name,
        n_boundaries=len(vm.layer_names) + 1,
        input_bytes=out_bytes[0],
        out_bytes=out_bytes,
        cum_flops=cum_flops,
        act_peak_bytes=act_peak,
        prefix_param_bytes=prefix_pb,
        model_param_bytes=tree_bytes(params),
        freeze_index=vm.freeze_index,
        headroom=headroom,
    )


def calibrate_profile(profile: LayerProfile, boundary: int,
                      measured_bytes: float, batch: int) -> LayerProfile:
    """The paper's hybrid calibration (§5.3): compare the static estimate
    against one measured run; any residual 'is assumed to grow
    proportionally with the batch size' and is folded into the per-sample
    activation figures. Always rounds UP (the over-estimation discipline).
    """
    import dataclasses

    est = profile.memory_estimate(boundary, batch)
    if measured_bytes <= est:
        return profile  # already safely over-estimating
    residual_per_sample = (measured_bytes - profile.prefix_param_bytes[boundary]) / batch
    scale = residual_per_sample / max(profile.act_peak_bytes[boundary], 1.0)
    return dataclasses.replace(
        profile,
        act_peak_bytes=[a * max(scale, 1.0) for a in profile.act_peak_bytes],
    )


def extrapolation_error(profile: LayerProfile, boundary: int,
                        measured_bytes: float, batch: int) -> float:
    """Paper §5.3's reported metric: % error of the batch-extrapolated
    estimate vs a measured run (they report 0.0005%–11.7%)."""
    est = profile.memory_estimate(boundary, batch) / (1 + profile.headroom)
    return 100.0 * abs(est - measured_bytes) / max(measured_bytes, 1.0)


def _layer_flops_estimate(layer_params, in_spec, out_spec) -> float:
    if not layer_params:
        return float(np.prod(out_spec.shape))  # elementwise
    w = layer_params.get("w") if isinstance(layer_params, dict) else None
    if w is not None and w.ndim == 4:  # conv HWIO
        spatial = np.prod(out_spec.shape[1:3])
        return float(2 * spatial * w.size)
    total = sum(2 * leaf.size for leaf in jax.tree.leaves(layer_params))
    seq = np.prod(in_spec.shape[1:-1]) if len(in_spec.shape) > 2 else 1
    return float(total * seq)
