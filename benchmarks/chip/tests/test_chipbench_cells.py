"""Each cell end to end at a small size on the CPU, with the Pallas
kernels in interpret mode: set-up from the seed, the window driving the
program's entry, the reference and the comparison. The runs must come
out correct and carry every end-to-end metric of their cell."""
import pytest

import chipbench_path  # noqa: F401
import tiny


@pytest.fixture(autouse=True)
def interpret(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")


@pytest.mark.parametrize("name,metrics", [
    ("paper-cnn.train", {"train_samples_per_s", "setup_s"}),
    ("paper-mclr.cohort", {"cohort_samples_per_s", "setup_s"}),
    ("paper-cnn.serve", {"serve_rps", "serve_p50_ms", "setup_s"}),
])
def test_cell_runs_correct(name, metrics):
    out = tiny.run(name)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == metrics
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "checks"
