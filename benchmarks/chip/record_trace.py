"""Record the small trace of the program with its phases named that the
CPU tests read (``testdata/tiny-scoped.xplane.pb``; ``tiny.xplane.pb``
was recorded the same way before the program named them).

    python3 benchmarks/chip/record_trace.py

On a TPU: the first cell of ``BENCHMARK.json`` (mamba2's fine-tune job)
at its small size from ``chipbench.testing`` (``small/``), set up as a
run sets it up, then two steps under the profiler with the benchmark's
own host spans.
"""
from __future__ import annotations

import pathlib
import shutil
import sys
import tempfile

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE)]


def main():
    import importlib.util

    import jax

    spec = importlib.util.spec_from_file_location("chipbench_run", HERE / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    from chipbench import cells, testing, xplane
    from chipbench.kinds import finetune

    run.enable_cache()
    run.device_or_exit(1)
    first = cells.load_benchmark()["workloads"][0]["name"]
    job = finetune.Job(testing.small_cell(first), 7)
    job.setup()
    job.window(0.0)                       # one untraced step first
    tmp = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        jax.profiler.start_trace(tmp)
        for _ in range(2):
            job.window(0.0, annotate=True)
        jax.profiler.stop_trace()
        out = HERE / "testdata" / "tiny-scoped.xplane.pb"
        shutil.copy(xplane.find_xplane(tmp), out)
        print(out, out.stat().st_size)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main()
