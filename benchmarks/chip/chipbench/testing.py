"""Small cells for the CPU tests: each configuration of the benchmark at
a width a test run can hold (the same keys, families and departures),
with its cell's own traffic at seq 32 x batch 4.

Their limits are set as the chip cells' are (limit = lower + 0.6
(upper - lower)), from readings at this size on the CPU: the program on
5 seeds from 3000000019 for the lower reading; the float8 control, and
the half-batch fault where it reads ten times the lower, on the first 3
for the upper; a state left unchanged reads 1. At this size the worst
leaf of mamba2's first gradient has no upper reading (the control reads
under twice the program), so, as on the chip, its median leaf is
compared instead."""
from __future__ import annotations

from chipbench import cells

SMALL = {
    "mamba2-1.3b": dict(n_layers=4, d_model=128, ssm_state=16, ssm_headdim=32,
                        ssm_chunk=16, vocab_size=512),
    "mistral-nemo-12b-8l": dict(n_layers=2, d_model=128, n_heads=4, n_kv_heads=2,
                                head_dim=32, d_ff=256, vocab_size=512, freeze_frac=0.5),
}


SMALL_LIMITS = {
    # program max / upper over the readings named above
    "mamba2-ft-2k": {"loss_gap": (1.40e-4, 5.55e-4), "grad_gap_median": (1.48e-3, 8.5e-3),
                     "update_gap": (0.135, 1.0)},
    "nemo8l-ft-2k": {"loss_gap": (1.00e-4, 4.30e-4), "grad_gap": (9.3e-4, 4.4e-3),
                     "update_gap": (2.6e-3, 1.0)},
}


def small_limits(name: str) -> dict:
    return {"numbers": {k: {"lower": lo, "upper": up, "limit": lo + 0.6 * (up - lo)}
                        for k, (lo, up) in SMALL_LIMITS[name].items()}}


def small_cell(name: str, seq_len: int = 32, batch: int = 4) -> cells.Cell:
    """The cell ``name`` of ``BENCHMARK.json`` at a small size."""
    bench = cells.load_benchmark()
    cfg_name = {w["name"]: w["config"] for w in bench["workloads"]}[name]
    cell = cells.resolve(name, bench)
    return cell._replace(
        config=dict(cell.config, **SMALL[cfg_name]),
        traffic=dict(cell.traffic, seq_len=seq_len, batch=batch),
        limits=small_limits(name))
