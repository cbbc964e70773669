"""One-chip smoke run of HAPI's two on-chip halves at mamba2-1.3b's
published widths, through the entry points a user calls.

    python chip_smoke.py

Phases, in one process:

1. Device check: the first device must be a TPU; otherwise exit non-zero.
2. Trainer: ``repro.launch.train.run_training`` (extract, optional int8
   boundary, tune, AdamW) for a few steps, without and with the int8
   boundary. Every loss must be finite, and the first step's loss must
   equal the unsplit model's loss on the same seeded params and batch.
   With the boundary on, the compiled step must hold the Pallas kernels.
3. Served path: a 2-replica ``HapiCluster`` whose storage servers run the
   jitted extract of the frozen prefix as their live executor; a burst of
   requests is drained, every response must carry int8 activations
   computed on the device, and one of them, dequantized, must match a
   direct ``forward_prefix`` of its object.

The last line of output is a JSON object naming the device. Weights and
data are random from fixed seeds; nothing is read from outside the
checkout. This is a smoke run, not a benchmark: the times it prints are
for one run.
"""
from __future__ import annotations

import json
import math
import pathlib
import statistics
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

ARCH = "mamba2-1.3b"
SEQ = 2048
TRAIN_BATCH = 4
TRAIN_STEPS = 4
OBJECT_SIZE = 4       # samples per stored object (one request each)
N_OBJECTS = 4
SEED = 0

# First-step loss against the unsplit model, relative. Without the
# boundary both programs run the same bf16 ops in a different program
# layout, so only rounding order differs. With it, the prefix output is
# rounded to int8 with one scale per 128 lanes (at most half a step,
# amax/254, per element) before the suffix runs.
LOSS_RTOL = {False: 1e-3, True: 5e-3}
# Served activations against a direct forward_prefix of the object, in
# int8 steps (amax/127) of each element's tile: half a step of int8
# rounding, plus 1.5 steps for the prefix itself. The extract program and
# the direct forward are compiled apart, and one bf16 ulp at the tile's
# largest value (amax/128) is about one step.
ACTS_MAX_STEPS = 2.0


def check(ok: bool, what) -> None:
    """Fail the run (non-zero exit, no result line); kept under ``-O``."""
    if not ok:
        raise SystemExit(f"chip_smoke: check failed: {what}")


def _peak_bytes(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else str(peak)


def check_device():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, found platform {dev.platform!r}")
    print(f"[device] {dev.device_kind} count={len(jax.devices())} "
          f"jax={jax.__version__}", flush=True)
    return dev


def unsplit_first_loss(arch: str, *, smoke: bool, seq: int, batch: int,
                       compress: bool) -> float:
    """The unsplit model's loss on the params and first batch that
    ``run_training`` starts from (same seed, same tier plan)."""
    import jax

    from repro.data.pipeline import COSDataPipeline
    from repro.launch.train import setup_training
    from repro.train.steps import init_split_params

    model, rc, plan, store = setup_training(
        arch, batch=batch, seq=seq, smoke=smoke, compress=compress)
    first = next(iter(COSDataPipeline(store, "train", global_batch=batch)))
    frozen, trainable = init_split_params(
        model, plan.split, jax.random.PRNGKey(rc.train.seed))
    loss = jax.jit(lambda f, t, b: model.loss(
        model.merge_params(f, t, plan.split), b))
    return float(loss(frozen, trainable, first))


def train_phase(arch: str, *, smoke: bool, seq: int, batch: int, steps: int,
                compress: bool) -> dict:
    import jax

    from repro.launch.train import run_training

    ref = unsplit_first_loss(arch, smoke=smoke, seq=seq, batch=batch,
                             compress=compress)
    out = run_training(arch, steps=steps, batch=batch, seq=seq, smoke=smoke,
                       compress=compress, log_every=steps)
    losses = out["losses"]
    check(len(losses) == steps, losses)
    check(all(math.isfinite(x) for x in losses), losses)
    rel = abs(losses[0] - ref) / abs(ref)
    check(rel <= LOSS_RTOL[compress], (losses[0], ref, rel))
    kernel = "tpu_custom_call" in out["compiled"].as_text()
    steady = statistics.median(out["step_seconds"][1:])
    print(f"[train compress={compress}] losses={losses} unsplit={ref} "
          f"rel_diff={rel:.3e} compile_s={out['compile_seconds']:.3f} "
          f"steady_step_s={steady:.4f} pallas_kernel={kernel} "
          f"peak_bytes_in_use={_peak_bytes(jax.devices()[0])}", flush=True)
    return {"losses": losses, "unsplit_loss": ref, "kernel_in_step": kernel,
            "compile_seconds": out["compile_seconds"],
            "steady_step_seconds": steady}


def serve_phase(arch: str, *, smoke: bool, seq: int, object_size: int,
                n_objects: int, seed: int = SEED) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.api import HapiCluster
    from repro.config import HapiConfig, ShapeConfig
    from repro.configs import get_config, get_smoke_config
    from repro.core.tier_split import make_extract_executor, plan_tiers
    from repro.data.pipeline import synthetic_dataset
    from repro.kernels import ops
    from repro.models.api import build_model
    from repro.train.steps import init_split_params

    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    model = build_model(cfg)
    shape = ShapeConfig("serve", "train", seq, object_size)
    hapi = HapiConfig(compress_transfer=True, cos_batch_min=1)
    plan = plan_tiers(cfg, shape, hapi, local_batch=object_size)
    frozen, _ = init_split_params(model, plan.split, jax.random.PRNGKey(seed))
    data = synthetic_dataset(cfg, shape, n_samples=object_size * n_objects,
                             seed=seed)
    executor = make_extract_executor(model, frozen, plan)
    cluster = (HapiCluster(seed=seed)
               .with_servers(2, n_accelerators=1)
               .with_dataset("tokens", data, object_size=object_size)
               .with_executor(arch, executor))
    ids = cluster.submit_burst("tokens", arch, tenant=0, hapi=hapi,
                               split=plan.split, train_batch=object_size)
    t = time.perf_counter()
    responses = cluster.drain()
    drain_s = time.perf_counter() - t
    check(sorted(r.req_id for r in responses) == sorted(ids), "responses")

    platform = jax.devices()[0].platform
    tile = math.gcd(cfg.d_model, ops.WIRE_TILE)
    for r in responses:
        q, scales = r.acts
        check(q.dtype == jnp.int8 and q.shape == (object_size, seq, cfg.d_model),
              q.shape)
        check(scales.shape == (object_size, seq, cfg.d_model // tile),
              scales.shape)
        check({d.platform for a in (q, scales) for d in a.devices()} == {platform},
              "activations not on the device")

    r = responses[0]
    q, scales = r.acts
    payload = cluster.store.objects[r.object_name].payload
    direct = jax.jit(model.forward_prefix, static_argnums=2)(
        frozen, payload, plan.split)
    deq = ops.dequantize_int8(q, scales, dtype=jnp.float32)
    step = np.repeat(np.asarray(scales), tile, axis=-1)
    err = np.abs(np.asarray(deq) - np.asarray(direct, np.float32))
    worst = float((err / step).max())
    check(worst <= ACTS_MAX_STEPS, worst)
    print(f"[serve] split={plan.split}/{cfg.n_blocks} responses={len(responses)} "
          f"cos_batches={[x.cos_batch for x in responses]} "
          f"extract_programs={sorted(executor.compiled)} drain_s={drain_s:.3f} "
          f"worst_err_int8_steps={worst:.4f} "
          f"peak_bytes_in_use={_peak_bytes(jax.devices()[0])}", flush=True)
    return {"responses": len(responses), "worst_steps": worst,
            "extract_programs": len(executor.compiled)}


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    dev = check_device()
    for compress in (False, True):
        out = train_phase(ARCH, smoke=False, seq=SEQ, batch=TRAIN_BATCH,
                          steps=TRAIN_STEPS, compress=compress)
        if compress:
            check(out["kernel_in_step"], "no Pallas kernel in the int8 step")
    serve_phase(ARCH, smoke=False, seq=SEQ, object_size=OBJECT_SIZE,
                n_objects=N_OBJECTS)
    import jax

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
