"""The timed path broken underneath the harness must turn ``correct``
false: a round that returns its state unchanged, a round that leaves
half of each device's batch out (means over the rest), a served answer
altered where it is produced. None of the cells exchanges data between
chips, so that fault has no place here."""
import pytest

import chipbench_path  # noqa: F401
import faults
import tiny


@pytest.fixture(autouse=True)
def interpret(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")


@pytest.mark.parametrize("name,fault", [
    ("paper-cnn.train", "unchanged"),
    ("paper-cnn.train", "half_batch"),
    ("paper-mclr.cohort", "unchanged"),
    ("paper-mclr.cohort", "half_batch"),
    ("paper-cnn.serve", "answer"),
])
def test_fault_turns_correct_false(name, fault):
    with faults.planted(fault):
        out = tiny.run(name)
    assert out["correct"] is False, out["checks"]
    # the run still reports what it measured and what it compared
    assert out["metrics"] and out["checks"]
