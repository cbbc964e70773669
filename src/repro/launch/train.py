"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-32b --smoke \
        --steps 50 --batch 8 --seq 64 --ckpt ckpt/

Wires every substrate layer together: COS object store -> resumable data
pipeline -> Hapi tier plan (Alg. 1 split + Eq. 4 COS batch) -> jit'd
Hapi train step -> AdamW -> atomic sharded checkpoints. ``--kill-at``
demonstrates fault tolerance (crash + exact-state resume). The driver
runs on one device: the smoke configs on CPU (default), the published
configs with ``--full`` on an accelerator that holds them whole
(``chip_smoke.py`` runs mamba2-1.3b this way on one TPU v5e).
"""
from __future__ import annotations

import argparse
import time
from typing import NamedTuple

import jax
import numpy as np

from repro.checkpoint.ckpt import restore_checkpoint, save_checkpoint
from repro.config import HapiConfig, RunConfig, ShapeConfig, TrainConfig
from repro.configs import get_config, get_smoke_config
from repro.core.tier_split import TierPlan, plan_tiers
from repro.cos.objectstore import ObjectStore
from repro.data.pipeline import COSDataPipeline, PipelineState, synthetic_dataset
from repro.launch.compile_cache import enable_compile_cache
from repro.models.api import build_model
from repro.models.transformer import Model
from repro.train.steps import build_hapi_train_step, init_train_state


class TrainSetup(NamedTuple):
    model: Model
    rc: RunConfig
    plan: TierPlan
    store: ObjectStore       # the seeded dataset, as COS objects


def setup_training(arch: str, *, steps: int = 50, batch: int = 8,
                   seq: int = 64, smoke: bool = True, compress: bool = False,
                   lr: float = 3e-4, object_size: int = 0,
                   dataset_batches: int = 4) -> TrainSetup:
    """Config, tier plan and seeded dataset of one training run."""
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    shape = ShapeConfig("custom", "train", seq, batch)
    hapi = HapiConfig(compress_transfer=compress, cos_batch_min=1)
    tc = TrainConfig(learning_rate=lr, total_steps=steps, warmup_steps=max(2, steps // 10))
    rc = RunConfig(model=cfg, shape=shape, hapi=hapi, train=tc)
    plan = plan_tiers(cfg, shape, hapi, local_batch=batch)

    # Dataset lives in the (simulated) COS as fixed-size objects.
    store = ObjectStore()
    data = synthetic_dataset(cfg, shape, n_samples=batch * dataset_batches,
                             seed=tc.seed)
    store.put_dataset("train", data, object_size=object_size or batch)
    return TrainSetup(build_model(cfg), rc, plan, store)


def run_training(
    arch: str,
    *,
    steps: int = 50,
    batch: int = 8,
    seq: int = 64,
    smoke: bool = True,
    ckpt_dir: str = "",
    ckpt_every: int = 20,
    kill_at: int = 0,
    compress: bool = False,
    lr: float = 3e-4,
    log_every: int = 5,
    object_size: int = 0,
    dataset_batches: int = 4,
):
    """Run the Hapi fine-tune loop. Returns the losses plus the compiled
    step (``compiled``), its AOT compile time (``compile_seconds``) and the
    wall time of each step up to its results being ready
    (``step_seconds``)."""
    model, rc, plan, store = setup_training(
        arch, steps=steps, batch=batch, seq=seq, smoke=smoke,
        compress=compress, lr=lr, object_size=object_size,
        dataset_batches=dataset_batches)
    cfg, tc = rc.model, rc.train
    print(f"[plan] split={plan.split}/{cfg.n_blocks} cos_batch={plan.cos_batch} "
          f"compress={plan.compress} ({plan.decision.reason})")
    pstate = PipelineState()

    state = init_train_state(model, rc, plan, jax.random.PRNGKey(tc.seed))
    start_step = 0
    if ckpt_dir:
        restored, extra, at = restore_checkpoint(ckpt_dir, state)
        if restored is not None:
            state, start_step = restored, at
            pstate = PipelineState.from_dict(extra.get("pipeline", {}))
            print(f"[resume] restored step {at}, object cursor {pstate.next_object}")

    step_fn = jax.jit(build_hapi_train_step(model, rc, plan), donate_argnums=(0,))
    compiled = None
    compile_seconds = 0.0

    pipe = COSDataPipeline(store, "train", global_batch=batch, state=pstate)
    it = iter(pipe)
    t0 = time.time()
    losses, step_seconds = [], []
    i = start_step
    while i < steps:
        try:
            raw = next(it)
        except StopIteration:
            it = iter(pipe)
            continue
        if compiled is None:
            t = time.perf_counter()
            compiled = step_fn.lower(state, raw).compile()
            compile_seconds = time.perf_counter() - t
        t = time.perf_counter()
        state, metrics = jax.block_until_ready(compiled(state, raw))
        step_seconds.append(time.perf_counter() - t)
        losses.append(float(metrics["loss"]))
        i += 1
        if i % log_every == 0 or i == steps:
            dt = time.time() - t0
            print(f"step {i:5d}  loss {losses[-1]:.4f}  lr {float(metrics['lr']):.2e} "
                  f"gnorm {float(metrics['grad_norm']):.3f}  {dt:.1f}s")
        if ckpt_dir and (i % ckpt_every == 0 or i == steps):
            save_checkpoint(ckpt_dir, i, state,
                            extra={"pipeline": pipe.state.to_dict(),
                                   "arch": arch, "loss": losses[-1]})
        if kill_at and i == kill_at:
            print(f"[kill] simulating crash at step {i}")
            return {"killed_at": i, "losses": losses}

    return {"final_loss": losses[-1], "losses": losses, "steps": i,
            "compiled": compiled, "compile_seconds": compile_seconds,
            "step_seconds": step_seconds}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--kill-at", type=int, default=0)
    ap.add_argument("--compress", action="store_true")
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args(argv)
    enable_compile_cache()
    out = run_training(
        args.arch, steps=args.steps, batch=args.batch, seq=args.seq,
        smoke=args.smoke, ckpt_dir=args.ckpt, ckpt_every=args.ckpt_every,
        kill_at=args.kill_at, compress=args.compress, lr=args.lr,
    )
    print({k: (round(v, 4) if isinstance(v, float) else v)
           for k, v in out.items()
           if k not in ("losses", "compiled", "step_seconds")})


if __name__ == "__main__":
    main()
