"""The control of each cell, at a size a test run holds: the plain
reference computed one precision step below the configuration (float32
training in bfloat16, the int8 store in int4), put in the program's
place, must fail the cell's limits, while the program passes them."""
import time

import pytest

import chipbench_path  # noqa: F401
import calibrate
import tiny
from chipbench import compare, harness


@pytest.fixture(autouse=True)
def interpret(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")


@pytest.mark.parametrize("name", ["paper-cnn.train", "paper-mclr.cohort",
                                  "paper-cnn.serve"])
def test_control_fails_the_limits(name):
    cell = harness.find_cell(name, overrides=tiny.TINY[name])
    harness.program_path()
    devices, peak = harness.chips_for(cell, allow_cpu=True)
    driver = harness.load_module(
        harness.BENCH_DIR / "drivers" / f"{cell.params['driver']}.py",
        "chipbench_driver_" + cell.params["driver"])
    ctx = harness.Context(cell, tiny.SEED, 0.5, False, devices, peak,
                          time.perf_counter())
    line = calibrate.readings(cell, driver, ctx, controls=True)
    limits = cell.params["limits"]
    assert compare.verdict(line["program"], limits)[0], line
    assert not compare.verdict(line["control"], limits)[0], line
