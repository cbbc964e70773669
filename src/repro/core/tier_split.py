"""TierPlan — the executable form of the paper's technique.

Combines the three decisions (split index, COS batch size, compression)
into a pair of pure functions:

  * ``extract(frozen, batch)``  — feature extraction of blocks [0, split)
    at *COS batch size* granularity (a scan over microbatches — the
    decoupled batch of §5.5), emitting the split-boundary activations,
    optionally int8-compressed for the wire (beyond-paper).
  * ``tune_loss(trainable, acts, batch)`` — the training side: remaining
    frozen blocks + trainable suffix + head, at the *training batch size*.

Both are jit-able and shard-able; the COS runtime and the tier-split
train step build on them.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import HapiConfig, ModelConfig, ShapeConfig
from repro.core.batch_adapt import AdaptRequest, adapt_batches
from repro.core.profiler import LayerProfile, profile_lm
from repro.core.splitter import SplitDecision, choose_split
from repro.kernels import ops
from repro.models.transformer import Model
from repro.obs import device_scope


@dataclass(frozen=True)
class TierPlan:
    split: int
    cos_batch: int            # samples per extraction microbatch
    compress: bool
    decision: SplitDecision

    @property
    def pushdown(self) -> bool:
        return self.split > 0


def largest_divisor_leq(n: int, cap: int) -> int:
    cap = max(1, min(cap, n))
    for d in range(cap, 0, -1):
        if n % d == 0:
            return d
    return 1


def plan_tiers(
    cfg: ModelConfig,
    shape: ShapeConfig,
    hapi: HapiConfig,
    *,
    profile: Optional[LayerProfile] = None,
    local_batch: Optional[int] = None,
) -> TierPlan:
    """Profile -> Alg. 1 split -> Eq. 4 batch adaptation -> TierPlan."""
    prof = profile or profile_lm(cfg, shape.seq_len, hapi.memory_headroom)
    decision = choose_split(prof, hapi, shape.global_batch)
    split = decision.split_index

    b = local_batch or shape.global_batch
    if split > 0:
        req = AdaptRequest(
            req_id=0,
            mem_per_sample=prof.act_peak_bytes[split] * (1 + prof.headroom),
            mem_model=prof.prefix_param_bytes[split],
            b_max=min(b, hapi.cos_batch),
        )
        res = adapt_batches([req], hapi.cos_hbm_budget, b_min=hapi.cos_batch_min)
        adapted = res.assignments[0].batch if res.assignments else hapi.cos_batch_min
    else:
        adapted = b
    cos_batch = largest_divisor_leq(b, adapted)
    return TierPlan(split=split, cos_batch=cos_batch,
                    compress=hapi.compress_transfer, decision=decision)


# ---------------------------------------------------------------------------
# Executable halves
# ---------------------------------------------------------------------------
def _split_batch(batch: dict, mb: int) -> Tuple[dict, int]:
    lead = next(iter(batch.values())).shape[0]
    nb = lead // mb
    assert lead % mb == 0, (lead, mb)
    return (
        jax.tree.map(lambda x: x.reshape(nb, mb, *x.shape[1:]), batch),
        nb,
    )


def make_extract_fn(model: Model, plan: TierPlan) -> Callable:
    """Feature extraction at COS-batch granularity (frozen => no grads)."""

    def extract(frozen, batch):
        mbatches, _ = _split_batch(batch, plan.cos_batch)

        def body(_, mb):
            acts = model.forward_prefix(frozen, mb, plan.split)
            acts = jax.lax.stop_gradient(acts)
            if plan.compress:
                return None, ops.quantize_int8(acts)
            return None, acts

        # The scan's own slicing and stacking count with the extract.
        with device_scope("hapi.extract"):
            _, out = jax.lax.scan(body, None, mbatches)
            # Re-flatten microbatch axis: (nb, mb, ...) -> (B, ...)
            return jax.tree.map(lambda x: x.reshape(-1, *x.shape[2:]), out)

    return extract


def make_extract_executor(model: Model, frozen: Any, plan: TierPlan) -> Callable:
    """The storage server's live executor ``fn(payload, split, cos_batch)``
    over the frozen prefix of ``plan``.

    The server grants the simulated Eq. 4 COS batch, which need not
    divide an object; the executor runs the largest microbatch that
    divides it and does not exceed the grant, with one jitted extract per
    microbatch size (``fn.compiled``, keyed by that size)."""
    compiled = {}

    def execute(payload, split, cos_batch):
        if split != plan.split:
            raise ValueError(f"executor holds the prefix to block "
                             f"{plan.split}, request asks for {split}")
        n = next(iter(payload.values())).shape[0]
        mb = largest_divisor_leq(n, cos_batch)
        if mb not in compiled:
            compiled[mb] = jax.jit(make_extract_fn(model, TierPlan(
                plan.split, mb, plan.compress, plan.decision)))
        return compiled[mb](frozen, payload)

    execute.compiled = compiled
    return execute


def make_tune_loss_fn(model: Model, plan: TierPlan) -> Callable:
    def tune_loss(trainable, acts, batch):
        if plan.compress:
            from repro.models.module import dtype_of

            q, scales = acts
            # Both backends (Pallas and ref) dequantize straight into the
            # model's compute dtype — no post-hoc .astype papering over a
            # hardcoded bf16 output.
            acts = ops.dequantize_int8(
                q, scales, dtype=dtype_of(model.cfg.compute_dtype))
        with device_scope("hapi.tune"):
            return model.loss_suffix(trainable, acts, batch, plan.split)

    return tune_loss


def wire_bytes(plan: TierPlan, acts: Any) -> int:
    """Actual bytes this activation payload puts on the bottleneck link."""
    leaves = jax.tree.leaves(acts)
    return sum(x.size * x.dtype.itemsize for x in leaves)
