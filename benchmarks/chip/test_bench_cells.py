"""Every cell of BENCHMARK.json resolves by name to its configuration,
traffic, limits, generator, reference and metric readers; the file keeps
to the benchmark's contract in the parts a test can see."""
import json
import re
import subprocess
import sys

import pytest

from chipbench import cells, peaks

BENCH = cells.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves_by_name(cell):
    c = cells.resolve(cell, BENCH)
    assert cells.kind_module(c.traffic).run
    assert cells.reference_module(c.config).layer
    assert cells.model_config(c.config).name == c.config["name"]
    assert set(c.limits["numbers"]) <= {"loss_gap", "grad_gap", "grad_gap_median",
                                        "update_gap"}
    for spec in c.limits["numbers"].values():
        assert spec["lower"] < spec["limit"] < spec["upper"]
        assert spec["upper"] >= 3 * spec["lower"]
    assert any(m["name"] == "setup_s" for m in c.end_to_end)
    assert len(c.end_to_end) >= 2 and c.per_layer
    reported = {m["name"] for m in c.end_to_end}
    for m in c.per_layer:
        assert callable(cells.metric_reader(m["name"]))
        assert m["moves"] in reported


@pytest.mark.parametrize("cell,config", [(w["name"], w["config"])
                                         for w in BENCH["workloads"]])
def test_cell_has_its_small_files(cell, config):
    """The CPU tests run every cell at a small size from files found by
    name: the sizes of its configuration and the limits of the cell."""
    from chipbench import testing

    c = cells.resolve(cell, BENCH)
    small = testing.small_config(config)
    assert small and set(small) <= set(c.config)
    limits = testing.small_limits(cell)
    assert set(limits["numbers"]) <= {"loss_gap", "grad_gap", "grad_gap_median",
                                      "update_gap"}
    for spec in limits["numbers"].values():
        assert spec["lower"] < spec["limit"] < spec["upper"]
    assert testing.small_cell(cell).limits == limits


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        cells.resolve("no-such-cell", BENCH)


def test_names_and_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    entries = BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"] + BENCH["per_layer"]
    names = [e["name"] for e in entries]
    assert len(names) == len(set(names))
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        with open(cells.ROOT / c["file"]) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"]
        assert c["file"].startswith(BENCH["paths"][0] + "/")
    layers = {}
    for m in BENCH["per_layer"]:
        layers.setdefault(m["layer"], set()).add(m["name"])
        assert m["source"] in ("device_trace", "program_span", "program_counter",
                               "host_clock")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_peak_table_refuses_an_unknown_kind():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def test_run_refuses_a_cpu_device():
    """Off a TPU the command exits non-zero and prints no result line."""
    cmd = [sys.executable, str(cells.BENCH_DIR / "run.py"), "--workload",
           BENCH["workloads"][0]["name"], "--seed", "3000000000", "--seconds", "1"]
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    p = subprocess.run(cmd, capture_output=True, text=True, env=env, timeout=300,
                       cwd=cells.ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "needs a TPU" in p.stderr


def test_run_fails_without_the_program(tmp_path):
    """In a directory holding only BENCHMARK.json and the benchmark's own
    files, the command exits non-zero and prints no result line."""
    import shutil

    shutil.copy(cells.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in BENCH["paths"]:
        shutil.copytree(cells.ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    cmd = [sys.executable, *BENCH["command"][1:], "--workload",
           BENCH["workloads"][0]["name"], "--seed", "7", "--seconds", "1"]
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    p = subprocess.run(cmd, capture_output=True, text=True, env=env, timeout=300,
                       cwd=tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout
