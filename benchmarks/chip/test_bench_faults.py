"""A whole run of each cell at a small size on the CPU, past the look for
a chip: sound, it is correct; with the timed path broken underneath, or
with the control (the reference in float8) in the program's place, it is
not. The faults are those a one-chip training cell can have: a step
that returns its state unchanged, and one that takes the mean over half
of its batch."""
import importlib.util

import jax
import pytest

from chipbench import cells, compare, testing
from chipbench.kinds import finetune

CELLS = [w["name"] for w in cells.load_benchmark()["workloads"]]
SEED = 3_000_000_019          # above 2**31, as the driver's seeds are


def _run_module():
    spec = importlib.util.spec_from_file_location("chipbench_run", cells.BENCH_DIR / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(cell_name, capsys):
    run = _run_module()
    dev = jax.devices()[0]
    result = run.run_cell(testing.small_cell(cell_name), SEED, 0.2, False, dev, 1, None)
    out = capsys.readouterr()
    assert out.out.strip().splitlines()[-1].startswith('{"correct": ')
    assert out.err.strip().splitlines()[-1].startswith("check failed_steps")
    assert list(result)[-1] == "checks"
    return result


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, capsys):
    result = _run(cell, capsys)
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"train_samples_per_s", "setup_s"}


def _unchanged(build):
    def builder(*a, **k):
        step = build(*a, **k)
        return lambda state, batch: (state, step(state, batch)[1])
    return builder


def _half_batch(build):
    def builder(*a, **k):
        step = build(*a, **k)

        def half(state, batch):
            b = next(iter(batch.values())).shape[0]
            return step(state, {key: v[: b // 2] for key, v in batch.items()})
        return half
    return builder


@pytest.mark.parametrize("fault", [_unchanged, _half_batch])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_step_is_not_correct(cell, fault, monkeypatch, capsys):
    monkeypatch.setattr(finetune, "build_hapi_train_step",
                        fault(finetune.build_hapi_train_step))
    result = _run(cell, capsys)
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_in_float8_is_not_correct(cell):
    """The reference in float8, put in the program's place, against the
    float32 reference: it has to fail one of the cell's numbers."""
    c = testing.small_cell(cell)
    job = finetune.Job(c, SEED)
    job.setup()
    job.free()
    numbers = compare.finetune_numbers(job.reference("fp8"), job.reference("f32"))
    assert not compare.judge(numbers, c.limits)[0], numbers
