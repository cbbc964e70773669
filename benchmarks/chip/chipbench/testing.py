"""Small cells for the CPU tests: each cell of ``BENCHMARK.json`` at a
width a test run can hold, with its own traffic at seq 32 x batch 4.

Both halves are found by name, so a new cell is tested once its files
exist:

- ``small/configs/<config>.json``: the sizes laid over the
  configuration (the same keys, family and departures otherwise);
- ``small/limits/<cell>.json``: the limits of the numbers that decide
  ``correct`` at that size, in the layout of ``limits/<cell>.json``,
  with the readings on the CPU and the rule they were set from.
"""
from __future__ import annotations

import json

from chipbench import cells

SMALL_DIR = cells.BENCH_DIR / "small"


def _load(kind: str, name: str) -> dict:
    with open(SMALL_DIR / kind / f"{name}.json") as f:
        return json.load(f)


def small_config(config_name: str) -> dict:
    """The sizes laid over configuration ``config_name`` at the small size."""
    return _load("configs", config_name)


def small_limits(cell_name: str) -> dict:
    """The limits of cell ``cell_name`` at the small size."""
    return _load("limits", cell_name)


def small_cell(name: str, seq_len: int = 32, batch: int = 4) -> cells.Cell:
    """The cell ``name`` of ``BENCHMARK.json`` at a small size."""
    bench = cells.load_benchmark()
    cfg_name = {w["name"]: w["config"] for w in bench["workloads"]}[name]
    cell = cells.resolve(name, bench)
    return cell._replace(
        config=dict(cell.config, **small_config(cfg_name)),
        traffic=dict(cell.traffic, seq_len=seq_len, batch=batch),
        limits=small_limits(name))
