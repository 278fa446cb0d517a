"""The LoRA cell end to end at a small size on the CPU, with the Pallas
kernels in interpret mode, as ``test_chipbench_cells`` runs the others:
set-up from the seed, the window driving the program's entry, the
reference and the comparison."""
import pytest

import chipbench_path  # noqa: F401
import tiny_cells


@pytest.fixture(autouse=True)
def interpret(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")


@pytest.mark.parametrize("name", sorted(tiny_cells.TINY))
def test_cell_runs_correct(name):
    out = tiny_cells.run(name)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "checks"
