import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("REPRO_XLA_EXTRA", "")
    + " --xla_force_host_platform_device_count="
    + os.environ.get("REPRO_DRYRUN_DEVICES", "512")
).strip()

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any jax import — jax locks the device
count on first backend init. Usage:

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--json out.json]

For each cell this proves the sharding config is coherent (lower+compile
succeed), prints/records ``memory_analysis()`` (fits per-chip HBM) and
``cost_analysis()`` (FLOPs/bytes), and extracts per-device collective
bytes from the partitioned HLO for the §Roofline terms.
"""
import argparse
import json
import re
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.config import (
    HW,
    HapiConfig,
    MeshSpec,
    RunConfig,
    ShapeConfig,
    SHAPES,
    TrainConfig,
    cell_is_runnable,
)
from repro.configs import ARCH_IDS, get_config
from repro.core.profiler import profile_lm
from repro.core.splitter import choose_split
from repro.core.tier_split import TierPlan, largest_divisor_leq
from repro.distributed.autoshard import activation_sharding
from repro.distributed.sharding import (
    Sharder,
    batch_pspecs,
    cache_pspecs,
    logits_pspec,
    opt_state_pspecs,
    param_pspecs,
)
from repro.launch import mesh as meshlib
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.specs import decode_specs, input_specs, param_specs
from repro.models.api import build_model
from repro.models.transformer import Model
from repro.optim.adamw import OptState
from repro.train.steps import (
    TrainState,
    build_decode_step,
    build_hapi_train_step,
    build_prefill_step,
)

# ---------------------------------------------------------------------------
# Roofline terms (collective/flops/bytes extraction lives in hlo_analysis.py)
# ---------------------------------------------------------------------------
def roofline_terms(flops: float, hbm_bytes: float, coll_bytes: float) -> Dict[str, float]:
    return {
        "compute_s": flops / HW.peak_flops_bf16,
        "memory_s": hbm_bytes / HW.hbm_bandwidth,
        "collective_s": coll_bytes / HW.ici_bandwidth,
    }


# ---------------------------------------------------------------------------
# Per-arch perf configs, applied with --perf.
# ---------------------------------------------------------------------------
# Only overrides that *won* their A/B: TP-only for the MoE arch whose FSDP
# gathers dominated; coarse extraction + fine accumulation for the 314B
# giant. Every other arch lowers with the defaults either way.
PERF_OVERRIDES = {
    "moonshot-v1-16b-a3b": {"train": {"fsdp": False}, "prefill": {"fsdp": False}},
    "whisper-small": {"train": {"fsdp": False}},
    "grok-1-314b": {"train": {"microbatch_div": 16, "cos_batch": 4}},
}


def perf_overrides(arch: str, kind: str) -> dict:
    per = PERF_OVERRIDES.get(arch, {})
    out = dict(per.get(None, {}))
    out.update(per.get(kind, {}))
    return out


# ---------------------------------------------------------------------------
# Cell construction
# ---------------------------------------------------------------------------
def _shardings(tree_pspecs, mesh):
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec), tree_pspecs,
        is_leaf=lambda x: isinstance(x, P),
    )


def plan_for_mesh(cfg, shape, hapi: HapiConfig, ms: MeshSpec) -> TierPlan:
    prof = profile_lm(cfg, shape.seq_len, hapi.memory_headroom)
    decision = choose_split(prof, hapi, shape.global_batch)
    split = decision.split_index
    sh = Sharder(ms)
    local_b = max(1, shape.global_batch // sh.data_size)
    # COS batch: HBM-budget-driven per data shard (conservative: activations
    # counted undivided by the model axis — the paper's over-estimation).
    per_sample = prof.act_peak_bytes[split] * (1 + prof.headroom)
    fit = int(max(1, (hapi.cos_hbm_budget * 0.5) / max(per_sample, 1.0)))
    local_cos = largest_divisor_leq(local_b, min(fit, local_b, hapi.cos_batch))
    return TierPlan(split=split, cos_batch=local_cos * sh.data_size,
                    compress=hapi.compress_transfer, decision=decision)


def lower_cell(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    fsdp: bool = True,
    compress: bool = False,
    microbatch_div: int = 8,
    donate: bool = True,
    cfg_override=None,
    cos_batch: int = 0,
) -> Dict[str, Any]:
    cfg = cfg_override or get_config(arch)
    shape = SHAPES[shape_name]
    if not cell_is_runnable(cfg, shape):
        return {"arch": arch, "shape": shape_name, "status": "skip",
                "reason": "long-context decode requires sub-quadratic arch"}

    ms = meshlib.mesh_spec(multi_pod=multi_pod)
    mesh = meshlib.make_mesh(ms)
    model = build_model(cfg)
    hapi = HapiConfig(compress_transfer=compress,
                      **({"cos_batch": cos_batch} if cos_batch else {}))
    t0 = time.time()

    if shape.kind == "train":
        micro = largest_divisor_leq(shape.global_batch,
                                    max(1, shape.global_batch // microbatch_div))
        if not cos_batch:
            # Fused extract+accumulate path (one chunk of activations live):
            # cap the COS batch at the accumulation chunk. Explicit
            # --cos-batch opts into the coarse-extraction path (grok).
            sh0 = Sharder(ms)
            hapi = HapiConfig(
                compress_transfer=compress,
                cos_batch=max(1, micro // sh0.data_size),
            )
        plan = plan_for_mesh(cfg, shape, hapi, ms)
        tc = TrainConfig(microbatch=micro,
                         opt_state_dtype="bfloat16" if "grok" in arch else "float32")
        rc = RunConfig(model=cfg, shape=shape, hapi=hapi, train=tc)
        pspec = param_specs(model)
        frozen_s, trainable_s = jax.eval_shape(
            lambda p: model.split_params(p, plan.split), pspec
        )
        sdt = jnp.bfloat16 if tc.opt_state_dtype == "bfloat16" else jnp.float32
        opt_s = OptState(
            m=jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, sdt), trainable_s),
            v=jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, sdt), trainable_s),
            step=jax.ShapeDtypeStruct((), jnp.int32),
        )
        state_s = TrainState(frozen_s, trainable_s, opt_s)
        state_sh = TrainState(
            param_pspecs(frozen_s, ms, fsdp=fsdp),
            param_pspecs(trainable_s, ms, fsdp=fsdp),
            OptState(
                opt_state_pspecs(opt_s.m, ms),
                opt_state_pspecs(opt_s.v, ms),
                P(),
            ),
        )
        batch_s = input_specs(cfg, shape)
        batch_sh = batch_pspecs(cfg, shape, ms)

        dp = Sharder(ms).dp(shape.global_batch)
        grad_specs = opt_state_pspecs(trainable_s, ms)

        def constrain(tree, kind):
            if kind == "acts":
                return jax.tree.map(
                    lambda x: jax.lax.with_sharding_constraint(
                        x, P(dp, *([None] * (x.ndim - 1)))
                    ),
                    tree,
                )
            return jax.tree.map(
                lambda x, sp: jax.lax.with_sharding_constraint(x, sp),
                tree, grad_specs,
            )

        step = build_hapi_train_step(model, rc, plan, constrain=constrain)
        jf = jax.jit(
            step,
            in_shardings=(_shardings(state_sh, mesh), _shardings(batch_sh, mesh)),
            out_shardings=(
                _shardings(state_sh, mesh),
                NamedSharding(mesh, P()),
            ),
            donate_argnums=(0,) if donate else (),
        )
        with mesh, activation_sharding(dp, model_size=ms.axis_size('model')):
            lowered = jf.lower(state_s, batch_s)
        extra = {"split": plan.split, "cos_batch": plan.cos_batch,
                 "microbatch": micro, "n_blocks": cfg.n_blocks}

    elif shape.kind == "prefill":
        step = build_prefill_step(model)
        pspec = param_specs(model)
        p_sh = param_pspecs(pspec, ms, fsdp=fsdp)
        batch_s = input_specs(cfg, shape)
        batch_sh = batch_pspecs(cfg, shape, ms)
        cache_s = jax.eval_shape(
            lambda p, b: step(p, b)[1], pspec, batch_s
        )
        cache_sh = cache_pspecs(cache_s, cfg, shape.global_batch, ms)
        lg_sh = logits_pspec(cfg, shape.global_batch, ms)
        jf = jax.jit(
            step,
            in_shardings=(_shardings(p_sh, mesh), _shardings(batch_sh, mesh)),
            out_shardings=(NamedSharding(mesh, lg_sh), _shardings(cache_sh, mesh)),
        )
        dp = Sharder(ms).dp(shape.global_batch)
        with mesh, activation_sharding(dp, model_size=ms.axis_size('model')):
            lowered = jf.lower(pspec, batch_s)
        extra = {"n_blocks": cfg.n_blocks}

    else:  # decode
        step = build_decode_step(model)
        pspec = param_specs(model)
        p_sh = param_pspecs(pspec, ms, fsdp=fsdp)
        cache_s, token_s, pos_s = decode_specs(model, cfg, shape)
        cache_sh = cache_pspecs(cache_s, cfg, shape.global_batch, ms)
        sh = Sharder(ms)
        dp = sh.dp(shape.global_batch)
        tok_sh = P(dp) if dp else P()
        lg_sh = logits_pspec(cfg, shape.global_batch, ms)
        jf = jax.jit(
            step,
            in_shardings=(
                _shardings(p_sh, mesh),
                _shardings(cache_sh, mesh),
                NamedSharding(mesh, tok_sh),
                NamedSharding(mesh, P()),
            ),
            out_shardings=(NamedSharding(mesh, lg_sh), _shardings(cache_sh, mesh)),
            donate_argnums=(1,) if donate else (),
        )
        with mesh, activation_sharding(dp, model_size=ms.axis_size('model')):
            lowered = jf.lower(pspec, cache_s, token_s, pos_s)
        extra = {"n_blocks": cfg.n_blocks}

    compiled = lowered.compile()
    t1 = time.time()

    ca = compiled.cost_analysis()
    ma = compiled.memory_analysis()
    hc = analyze_hlo(compiled.as_text())   # trip-count-aware, per device
    colls = hc.coll_by_kind
    coll_total = hc.coll_bytes
    flops = hc.flops
    hbm = hc.bytes
    terms = roofline_terms(flops, hbm, coll_total)
    dominant = max(terms, key=terms.get)

    # MODEL_FLOPS: 6*N*D train / 2*N*D prefill / 2*N*B decode (N_active for
    # MoE); step-aware variant separates the fwd-only frozen prefix.
    n_act = cfg.param_count(active_only=True)
    if shape.kind == "train":
        tokens = shape.global_batch * (shape.seq_len if cfg.family != "encdec"
                                       else shape.seq_len + cfg.dec_seq)
        model_flops = 6.0 * n_act * tokens
        fz = extra.get("split", 0) / max(cfg.n_blocks, 1)
        model_flops_step = (2.0 + 4.0 * (1 - fz)) * n_act * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2.0 * n_act * tokens
        model_flops_step = model_flops
    else:
        tokens = shape.global_batch
        model_flops = 2.0 * n_act * tokens
        model_flops_step = model_flops
    hlo_global = flops * ms.n_devices
    ratio = model_flops / hlo_global if hlo_global else 0.0
    ratio_step = model_flops_step / hlo_global if hlo_global else 0.0

    mem = {}
    if ma is not None:
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes"):
            mem[attr] = getattr(ma, attr, None)

    result = {
        "arch": arch, "shape": shape_name, "status": "ok",
        "mesh": "x".join(map(str, ms.shape)),
        "n_devices": ms.n_devices,
        "compile_s": round(t1 - t0, 1),
        "flops_per_device": flops,
        "hbm_bytes_per_device": hbm,
        "collective_bytes_per_device": coll_total,
        "collectives": colls,
        "roofline": terms,
        "dominant": dominant,
        "memory_analysis": mem,
        "xla_cost_analysis_flops": float(ca.get("flops", 0.0)),
        "model_flops_6nd": model_flops,
        "model_flops_step": model_flops_step,
        "useful_ratio_6nd": ratio,
        "useful_ratio_step": ratio_step,
        "fsdp": fsdp,
        **extra,
    }
    return result


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--compress", action="store_true")
    ap.add_argument("--microbatch-div", type=int, default=8)
    ap.add_argument("--cos-batch", type=int, default=0)
    ap.add_argument("--perf", action="store_true",
                    help="apply PERF_OVERRIDES")
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)

    cells = []
    if args.all:
        for a in ARCH_IDS:
            for s in SHAPES:
                cells.append((a, s))
    else:
        cells.append((args.arch, args.shape))

    results = []
    for arch, shape_name in cells:
        try:
            kw = dict(fsdp=not args.no_fsdp, compress=args.compress,
                      microbatch_div=args.microbatch_div, cos_batch=args.cos_batch)
            if args.perf:
                kw.update(perf_overrides(arch, SHAPES[shape_name].kind))
            r = lower_cell(arch, shape_name, multi_pod=args.multi_pod, **kw)
        except Exception as e:  # a failing cell is a bug in the system
            r = {"arch": arch, "shape": shape_name, "status": "FAIL",
                 "error": f"{type(e).__name__}: {e}",
                 "trace": traceback.format_exc()[-2000:]}
        results.append(r)
        tag = r["status"]
        if tag == "ok":
            t = r["roofline"]
            print(f"[{tag}] {arch:24s} {shape_name:12s} mesh={r['mesh']:9s} "
                  f"compile={r['compile_s']:6.1f}s flops/dev={r['flops_per_device']:.3e} "
                  f"comp={t['compute_s']:.4f}s mem={t['memory_s']:.4f}s "
                  f"coll={t['collective_s']:.4f}s dom={r['dominant']} "
                  f"useful={r['useful_ratio_step']:.2f}")
            if r["memory_analysis"]:
                print(f"      memory_analysis: {r['memory_analysis']}")
        elif tag == "skip":
            print(f"[{tag}] {arch:24s} {shape_name:12s} — {r['reason']}")
        else:
            print(f"[{tag}] {arch:24s} {shape_name:12s} — {r['error']}")
        sys.stdout.flush()

    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1, default=str)
    n_fail = sum(1 for r in results if r["status"] == "FAIL")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
