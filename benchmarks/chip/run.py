"""Run one cell of the on-chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Reads the cell from ``BENCHMARK.json`` at the checkout's root, enables
JAX's persistent compilation cache inside the checkout, refuses to run
on anything but the TPU chips the cell asks for, then: set-up (seeded
weights on the device, the compiled step or server, warm-up), a window
of ``--seconds``, the peak device memory, and the comparison with the
plain reference that decides ``correct``. With ``--trace 1`` the window
runs under the profiler and the cell's per-layer metrics are read from
the trace; otherwise its end-to-end metrics are reported.

The numbers compared are printed, each beside its limit, as the last
lines on standard error, and the last line on standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (``breakdown`` with ``--trace 1``) and, last, ``checks``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_or_exit(chips: int):
    """The first device, if the process sees at least ``chips`` TPUs;
    otherwise exit non-zero before any result is printed."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"run.py: needs a TPU, found platform {devs[0].platform!r}")
    if len(devs) < chips:
        sys.exit(f"run.py: the cell needs {chips} chips, found {len(devs)}")
    return devs[0], len(devs)


def enable_cache():
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def per_layer(cell, out: dict, trace_dir: str, peaks: dict):
    """Read the trace and every per-layer metric of the cell."""
    from chipbench import cells, xplane

    path = xplane.find_xplane(trace_dir)
    tr = xplane.load(path)
    lo, hi = xplane.window(tr)
    busy = xplane.busy_ns(tr, lo, hi) * 1e-9
    ctx = dict(out["counts"], trace=tr, trace_path=path, lo=lo, hi=hi,
               window_s=(hi - lo) * 1e-9, busy_s=busy, steps=out["window"]["steps"],
               peaks=peaks)
    metrics = {}
    for m in cell.per_layer:
        value = cells.metric_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics, busy, ctx["window_s"], xplane.breakdown(tr, lo, hi)


def run_cell(cell, seed: int, seconds: float, trace: bool, dev, count: int,
             peaks) -> dict:
    """Everything after the look for a chip: run the cell once, print the
    compared numbers and the result line, and return the result."""
    from chipbench import cells, compare

    kind = cells.kind_module(cell.traffic)
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        out = kind.run(cell, seed, seconds, trace_dir=trace_dir)
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": count, "memory_peak_bytes": out["memory_peak_bytes"]}
        result = {"correct": None, "attempted": out["attempted"],
                  "failed": out["failed"]}
        if trace:
            metrics, busy, win, brk = per_layer(cell, out, trace_dir, peaks)
            device.update(busy_s=busy, window_s=win)
            result.update(metrics=metrics, device=device, breakdown=brk)
        else:
            e2e = dict(out["end_to_end"], setup_s=out["setup_done"] - T_START)
            result.update(metrics={m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                                   for m in cell.end_to_end},
                          device=device)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    correct, rows = compare.judge(out["numbers"], cell.limits)
    rows.append(("failed_steps", out["failed"], 0))
    result["correct"] = bool(correct and out["failed"] == 0)
    result["checks"] = {name: {"value": v, "limit": lim} for name, v, lim in rows}
    for name, v, lim in rows:
        print(f"check {name} = {v!r} limit {lim!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return result


def main(argv=None) -> dict:
    args = parse(argv)
    sys.path[:0] = [str(ROOT / "src"), str(HERE)]
    from chipbench import cells, peaks

    cell = cells.resolve(args.workload)
    enable_cache()
    dev, count = device_or_exit(cell.chips)
    return run_cell(cell, args.seed, args.seconds, bool(args.trace), dev, count,
                    peaks.peaks_for(dev.device_kind))


if __name__ == "__main__":
    main()
