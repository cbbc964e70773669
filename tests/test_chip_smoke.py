"""chip_smoke.py's phases at the mamba2 smoke config on the CPU, and its
refusal to run without a TPU."""
import importlib.util
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

ARCH = "mamba2-1.3b"


@pytest.mark.parametrize("compress", [False, True])
def test_train_phase_smoke(compress):
    out = chip_smoke.train_phase(ARCH, smoke=True, seq=32, batch=4, steps=3,
                                 compress=compress)
    assert len(out["losses"]) == 3
    # Off-TPU the int8 boundary runs the XLA reference, not the kernel.
    assert not out["kernel_in_step"]
    assert out["compile_seconds"] > 0


def test_serve_phase_smoke():
    out = chip_smoke.serve_phase(ARCH, smoke=True, seq=32, object_size=4,
                                 n_objects=4)
    assert out["responses"] == 4
    assert out["extract_programs"] >= 1


def test_check_device_refuses_cpu():
    with pytest.raises(SystemExit):
        chip_smoke.check_device()


@pytest.mark.parametrize("alone", [False, True])
def test_script_fails_without_tpu(alone, tmp_path):
    """Run on the CPU, or copied into a directory without the repo, the
    script exits non-zero and prints no result line."""
    cwd = ROOT
    if alone:
        shutil.copy(ROOT / "chip_smoke.py", tmp_path)
        cwd = tmp_path
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
