"""The general generator of split fine-tune jobs (traffic ``kind``
"finetune").

One job, as the program's training driver runs it: the seeded rows sit
in the program's object store as objects, its ``COSDataPipeline`` feeds
them, and the jitted ``build_hapi_train_step`` (extract, int8 boundary,
tune, AdamW) runs one step per batch, each ended by
``block_until_ready``. The mix's file gives the sizes and the optimizer:
``seq_len``, ``batch``, ``compress``, ``dataset_batches``,
``check_steps`` and the AdamW settings.

Set-up builds one compiled step with its state and drives it through its
first ``check_steps`` steps, through the same call and feed that the
window then goes on with. Those steps are what the plain reference
follows once the window has closed.
"""
from __future__ import annotations

import gc
import math
import sys
import time
from contextlib import nullcontext

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.config import HapiConfig, RunConfig, ShapeConfig, TrainConfig
from repro.core.tier_split import make_extract_fn, plan_tiers
from repro.cos.objectstore import ObjectStore
from repro.data.pipeline import COSDataPipeline
from repro.models.api import build_model
from repro.optim.adamw import init_opt_state
from repro.train.steps import TrainState, build_hapi_train_step

from chipbench import cells, compare, counts, weights
from chipbench.reference.lm import LMReference, leaf_norms

_TRAIN_KEYS = ("learning_rate", "weight_decay", "beta1", "beta2", "eps",
               "grad_clip", "warmup_steps", "total_steps")


def run_config(cell):
    """The program's run config and tier plan for this cell."""
    cfg = cells.model_config(cell.config)
    t = cell.traffic
    shape = ShapeConfig(cell.name, "train", t["seq_len"], t["batch"])
    hapi = HapiConfig(compress_transfer=t["compress"], cos_batch_min=1)
    tc = TrainConfig(**{k: t[k] for k in _TRAIN_KEYS})
    rc = RunConfig(model=cfg, shape=shape, hapi=hapi, train=tc)
    return rc, plan_tiers(cfg, shape, hapi, local_batch=t["batch"])


class Job:
    """One run of a fine-tune cell: ``setup``, ``window``, ``check``."""

    def __init__(self, cell, seed: int):
        self.cell, self.seed = cell, seed
        self.rc, self.plan = run_config(cell)
        self.cfg, self.t = self.rc.model, cell.traffic
        self.model = build_model(self.cfg)
        self.tie = self.cfg.tie_embeddings
        self.full_params = weights.full_params_fn(self.model)

    # -- set-up ---------------------------------------------------------
    def _params(self):
        """``(frozen, trainable)`` from the seed, in one jitted call; made
        again for the reference after the window."""
        split, tie, full = self.plan.split, self.tie, self.full_params
        return jax.jit(lambda k: weights.split_full(full(k), split, tie))(
            weights.seed_key(self.seed))

    def setup(self):
        t, model, rc, plan = self.t, self.model, self.rc, self.plan
        frozen, trainable = self._params()
        state = TrainState(frozen, trainable, init_opt_state(trainable, rc.train))
        rows = weights.token_rows(self.seed, t["dataset_batches"] * t["batch"],
                                  t["seq_len"], self.cfg.vocab_size)
        store = ObjectStore()
        store.put_dataset("train", {"tokens": rows, "labels": rows.copy()},
                          object_size=t["batch"])
        self.pipe = COSDataPipeline(store, "train", global_batch=t["batch"])
        self.it = iter(self.pipe)
        step = jax.jit(build_hapi_train_step(model, rc, plan), donate_argnums=(0,))
        first = self._next()
        self.compiled = step.lower(state, first).compile()
        self.boundary_bytes = self.boundary_bytes_per_sample(frozen, first)

        # The checked steps: the window's own call and feed.
        self.check_batches, losses = [], []
        raw = first
        for i in range(t["check_steps"]):
            raw = raw if i == 0 else self._next()
            self.check_batches.append(np.array(raw["tokens"]))
            state, metrics = self.compiled(state, raw)
            losses.append(float(metrics["loss"]))
            if i == 0:
                gnorm = float(metrics["grad_norm"])
                clip = min(1.0, rc.train.grad_clip / max(gnorm, 1e-9))
                first_m = leaf_norms(state.opt.m)
                grad_norms = {k: v / ((1 - rc.train.beta1) * clip)
                              for k, v in first_m.items()}
        self.prog = {"losses": losses, "grad_norms": grad_norms}
        self.theta_checked = jax.device_get(state.trainable)
        self.state = jax.block_until_ready(state)

    def _next(self):
        try:
            return next(self.it)
        except StopIteration:
            self.it = iter(self.pipe)
            return next(self.it)

    # -- the measured window ----------------------------------------------
    def window(self, seconds: float, annotate: bool = False) -> dict:
        span = TraceAnnotation if annotate else (lambda name: nullcontext())
        state, steps, bad, ends = self.state, 0, 0, []
        t0 = time.perf_counter()
        while True:
            with span("bench.data_next"):
                raw = self._next()
            with span("bench.step_dispatch"):
                state, metrics = self.compiled(state, raw)
            with span("bench.step_wait"):
                jax.block_until_ready((state, metrics))
            steps += 1
            bad += not math.isfinite(float(metrics["loss"]))
            ends.append(time.perf_counter())
            elapsed = ends[-1] - t0
            if elapsed >= seconds:
                break
        self.state = state
        samples = steps * self.t["batch"]
        each = np.diff([t0] + ends)
        return {"steps": steps, "failed_steps": bad, "elapsed": elapsed,
                "samples": samples, "samples_per_s": samples / elapsed,
                "step_s": [float(np.min(each)), float(np.median(each)),
                           float(np.max(each))]}

    def free(self):
        """Drop the program's state and compiled step."""
        self.state = self.compiled = None
        gc.collect()

    # -- what the per-layer readers get -----------------------------------
    def boundary_bytes_per_sample(self, frozen, batch) -> float:
        """Bytes per sample of the extract's output, from the shapes of
        the frozen part and a batch (arrays or ``ShapeDtypeStruct``s)."""
        out = jax.eval_shape(make_extract_fn(self.model, self.plan), frozen, batch)
        return sum(math.prod(x.shape) * x.dtype.itemsize
                   for x in jax.tree.leaves(out)) / self.t["batch"]

    def counts(self) -> dict:
        t = self.t
        per_sample = counts.finetune_flops_per_sample(
            self.cell.config, t["seq_len"], self.plan.split)
        elems = counts.boundary_elements(t["batch"], t["seq_len"], self.cfg.d_model)
        return {
            "flops_per_step": per_sample["total"] * t["batch"],
            "quantize_bytes_per_step": counts.quantize_bytes(elems) if t["compress"] else 0,
            "dequantize_bytes_per_step": counts.dequantize_bytes(elems) if t["compress"] else 0,
            "boundary_bytes_per_sample": self.boundary_bytes,
        }

    # -- the comparison with the plain reference ---------------------------
    def reference(self, precision: str = "f32", rows=None) -> dict:
        """The plain reference over the checked steps (call after
        ``free``): ``precision="fp8"`` is the control, ``rows`` picks the
        rows of each step (a planted fault)."""
        frozen, trainable = self._params()
        ref = LMReference(cells.reference_module(self.cell.config),
                          self.cell.config, precision)
        with jax.default_matmul_precision("highest"):
            return ref.train_steps(frozen, trainable, self.check_batches,
                                   self.t, self.t["compress"], rows=rows)

    def program(self) -> dict:
        """The program's readings over the checked steps."""
        _, trainable = self._params()
        delta = jax.jit(lambda a, b: jax.tree.map(
            lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))
        d = leaf_norms(delta(jax.device_put(self.theta_checked), trainable))
        return dict(self.prog, delta_norms=d)

    def check(self) -> dict:
        """The numbers that decide ``correct``."""
        prog = self.program()
        ref = self.reference()
        self.ref_losses = ref["losses"]
        if list(prog["grad_norms"]) != list(ref["grad_norms"]):
            raise ValueError("the program's and the reference's leaves differ")
        return compare.finetune_numbers(prog, ref)


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def run(cell, seed: int, seconds: float, trace_dir=None) -> dict:
    """Set-up, window and check of one run. ``trace_dir``: record the
    window with the profiler there."""
    t0 = time.perf_counter()
    job = Job(cell, seed)
    job.setup()
    setup_done = time.perf_counter()
    log(f"job built and set up in {setup_done - t0:.3f} s; split "
        f"{job.plan.split}/{job.cfg.n_blocks}, cos batch {job.plan.cos_batch}, "
        f"checked losses {job.prog['losses']}")
    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir)
    try:
        win = job.window(seconds, annotate=trace_dir is not None)
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
    out = {
        "setup_done": setup_done,
        "attempted": win["steps"], "failed": win["failed_steps"],
        "end_to_end": {"train_samples_per_s": win["samples_per_s"]},
        "window": win, "counts": job.counts(),
        "memory_peak_bytes": _peak_bytes(),
    }
    log(f"window {win}; memory_peak_bytes {out['memory_peak_bytes']}")
    job.free()
    t = time.perf_counter()
    out["numbers"] = job.check()
    log(f"reference and comparison {time.perf_counter() - t:.3f} s; "
        f"reference losses {job.ref_losses}")
    return out


def _peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")
