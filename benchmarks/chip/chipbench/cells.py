"""Resolve a cell of ``BENCHMARK.json`` to its files, by name.

A cell names a configuration and a traffic mix. Everything that belongs
to one of them sits in a file of its own, found here by name:

- ``configs/<config>.json`` (the path given in ``BENCHMARK.json``): the
  configuration as it is run, its source, ``reduced``, ``assumed``, the
  deployment it stands for and the plain reference that checks it
  (``reference`` names ``chipbench/reference/<name>.py``, which gives
  ``layer``, the block the reference runs, and ``block_flops`` and
  ``layers_per_block``, what ``chipbench/counts.py`` counts);
- ``traffic/<traffic>.json``: the mix's parameters; its ``kind`` names
  the general generator ``chipbench/kinds/<kind>.py`` that reads them;
- ``limits/<cell>.json``: the limits of the numbers that decide
  ``correct``, with the readings they were set from;
- ``metrics/<metric>.py``: one reader per per-layer metric;
- ``small/configs/<config>.json`` and ``small/limits/<cell>.json``: the
  sizes and limits of the cell at a width the CPU tests can hold
  (``chipbench/testing.py``), which run every cell of ``BENCHMARK.json``.

So a later cell, configuration, mix or metric is new files and new
entries in ``BENCHMARK.json`` (a cell's name also goes into the
``workloads`` of each per-layer metric it reports), and no edit of a
file that is there, as long as the program builds the configuration and
a generator of its ``kind`` exists.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import pathlib
from typing import Callable, NamedTuple, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]


# What a configuration's plain reference module has to give.
REFERENCE_API = ("layer", "block_flops", "layers_per_block")


class Cell(NamedTuple):
    name: str
    chips: int
    config: dict           # the configuration file's contents
    traffic: dict          # the traffic file's contents
    limits: dict           # the limits file's contents
    end_to_end: list       # BENCHMARK.json metric entries this cell reports
    per_layer: list


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, bench: Optional[dict] = None,
            root: pathlib.Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration, traffic and limits
    loaded; ``KeyError`` for an unknown cell, ``FileNotFoundError`` for a
    missing file, ``AttributeError`` for a reference module that lacks a
    name of ``REFERENCE_API``: all before any set-up."""
    bench = bench if bench is not None else load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(root / configs[w["config"]]["file"]) as f:
        config = json.load(f)
    ref = reference_module(config)
    missing = [a for a in REFERENCE_API if not hasattr(ref, a)]
    if missing:
        raise AttributeError(f"reference {config['reference']!r} of configuration "
                             f"{w['config']!r} lacks {missing}")
    with open(BENCH_DIR / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    with open(BENCH_DIR / "limits" / f"{name}.json") as f:
        limits = json.load(f)
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        limits=limits,
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)])


def kind_module(traffic: dict):
    """The general generator that runs mixes of ``traffic['kind']``."""
    return importlib.import_module(f"chipbench.kinds.{traffic['kind']}")


def reference_module(config: dict):
    """The plain reference named by the configuration file."""
    return importlib.import_module(f"chipbench.reference.{config['reference']}")


def metric_reader(name: str) -> Callable:
    """``read(ctx)`` of ``metrics/<name>.py``."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def model_config(config: dict):
    """The program's ``ModelConfig`` from a configuration file: every key
    that names a ``ModelConfig`` field is passed on, the rest is the
    benchmark's own (source, cut, deployment, departures)."""
    from repro.config import ModelConfig

    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    return ModelConfig(**{k: v for k, v in config.items() if k in fields})
