"""Read the numbers that decide ``correct`` on many seeds in one process,
with the control and the planted faults, to set the limits from.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --first-seed <n> --seeds 12 --control-seeds 3 [--out FILE]

For each seed: set-up as a run makes it (the checked steps through the
compiled step), then the program's numbers against the plain reference.
For the first ``--control-seeds`` seeds also the control (the reference
in float8 in the program's place), the reference with bf16 products (to
tell rounding from a fault where the program reads high) and the
half-batch fault (the reference on half of each step's rows) against the
reference. A step
that returns its state unchanged reads 1 on ``update_gap`` by
construction and needs no run. One JSON line per seed, with the three
leaves that read worst and every leaf's norms. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE)]


def worst_leaves(prog: dict, ref: dict, key: str, n: int = 3) -> list:
    import statistics

    med = statistics.median(ref[key].values())
    gaps = {k: abs(prog[key][k] - v) / max(v, med) for k, v in ref[key].items()}
    return sorted(gaps.items(), key=lambda kv: -kv[1])[:n]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import importlib.util

    spec = importlib.util.spec_from_file_location("chipbench_run", HERE / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    from chipbench import cells, compare
    from chipbench.kinds import finetune

    cell = cells.resolve(args.workload)
    run.enable_cache()
    run.device_or_exit(cell.chips)
    sink = open(args.out, "a") if args.out else None
    for i in range(args.seeds):
        seed = args.first_seed + i
        t = time.perf_counter()
        job = finetune.Job(cell, seed)
        job.setup()
        job.free()
        prog = job.program()
        ref = job.reference()
        row = {"cell": cell.name, "seed": seed, "kind": "program",
               "numbers": compare.finetune_numbers(prog, ref),
               "losses": [prog["losses"], ref["losses"]],
               "worst_grad": worst_leaves(prog, ref, "grad_norms"),
               "worst_update": worst_leaves(prog, ref, "delta_norms"),
               "readings": prog, "reference": ref}
        rows = [row]
        if i < args.control_seeds:
            for kind, got in (("control_fp8", job.reference("fp8")),
                              ("reference_bf16", job.reference("bf16")),
                              ("half_batch", job.reference(
                                  rows=lambda b: list(range(b // 2))))):
                rows.append({"cell": cell.name, "seed": seed, "kind": kind,
                             "numbers": compare.finetune_numbers(got, ref),
                             "worst_grad": worst_leaves(got, ref, "grad_norms"),
                             "worst_update": worst_leaves(got, ref, "delta_norms"),
                             "readings": got})
        for r in rows:
            r["seconds"] = time.perf_counter() - t
            line = json.dumps(r)
            print(line, flush=True)
            if sink:
                sink.write(line + "\n")
                sink.flush()
        del job
    if sink:
        sink.close()


if __name__ == "__main__":
    main()
