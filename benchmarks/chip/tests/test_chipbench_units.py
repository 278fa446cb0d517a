"""The benchmark's arithmetic: percentile and spread, the open-loop
schedule and lateness, the FLOP and byte counts worked by hand from the
shapes, and the table of peaks."""
import math
import os
import subprocess
import sys

import numpy as np
import pytest

import chipbench_path  # noqa: F401
from chipbench import flops, harness, stats, traffic

CNN = {"kind": "cnn", "input_shape": [28, 28, 1], "num_classes": 10,
       "conv_channels": [16, 32], "hidden": [128]}
MCLR = {"kind": "mclr", "input_shape": [60], "num_classes": 10}
V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_percentile_is_nearest_rank():
    vals = list(range(1, 101))
    assert stats.percentile(vals, 99) == 99
    assert stats.percentile(vals, 50) == 50
    assert stats.percentile(reversed(vals), 100) == 100
    assert stats.percentile([7.5], 99) == 7.5
    assert stats.percentile([3, 1, 2], 0) == 1
    assert math.isnan(stats.percentile([], 50))


def test_poisson_schedule():
    a = traffic.poisson_schedule(1000.0, 4.0, traffic.rng_for(2**40 + 3, 1))
    b = traffic.poisson_schedule(1000.0, 4.0, traffic.rng_for(2**40 + 3, 1))
    c = traffic.poisson_schedule(1000.0, 4.0, traffic.rng_for(2**40 + 4, 1))
    assert np.array_equal(a, b) and not np.array_equal(a[:10], c[:10])
    assert np.all(np.diff(a) >= 0) and a[0] >= 0 and a[-1] < 4.0
    # the count is fixed; the gaps are exponential with mean 1 / rate
    assert len(a) == len(c) == 4000
    gaps = np.diff(a)
    assert abs(gaps.mean() - 1e-3) < 5 * 1e-3 / math.sqrt(len(gaps))
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.1
    with pytest.raises(ValueError):
        traffic.poisson_schedule(0.0, 1.0, traffic.rng_for(1, 1))


def test_zipf_tags():
    rng = traffic.rng_for(5, 2)
    t, d = traffic.zipf_tags(8, 512, 20000, rng, alpha=1.2,
                             unknown_frac=0.05)
    assert t.dtype == np.int32 and d.dtype == np.int32
    unknown = d == 513
    assert abs(unknown.mean() - 0.05) < 0.01
    assert np.all((d[~unknown] >= 0) & (d[~unknown] < 512))
    assert np.all((t[~unknown] >= 0) & (t[~unknown] < 8))
    assert set(np.unique(t[unknown])) <= set(range(8)) | {9}
    # skewed: the most popular device takes far more than 1/4096
    _, counts = np.unique(t[~unknown] * 512 + d[~unknown],
                          return_counts=True)
    assert counts.max() / (~unknown).sum() > 0.1


def test_batching_and_lateness():
    assert traffic.pad_size(1, (8, 16, 32)) == 8
    assert traffic.pad_size(9, (32, 8, 16)) == 16
    assert traffic.pad_size(32, (8, 16, 32)) == 32
    with pytest.raises(ValueError):
        traffic.pad_size(33, (8, 16, 32))
    due = np.array([0.0, 0.001, 0.002])
    sent = np.array([0.0005, 0.0015, 0.0040])
    done = np.array([0.0030, 0.0030, 0.0060])
    assert traffic.lateness_ms(due, sent) == pytest.approx([0.5, 0.5, 2.0])
    assert traffic.latency_ms(due, done) == pytest.approx([3.0, 2.0, 4.0])


def test_cnn_flops_by_hand():
    # im2col products: conv0 (784 x 9) @ (9 x 16), conv1 (196 x 144) @
    # (144 x 32), dense 1568 -> 128 -> 10
    assert flops.layers(CNN) == [(784, 9, 16), (196, 144, 32),
                                 (1, 1568, 128), (1, 128, 10)]
    fwd = 2 * (784 * 9 * 16 + 196 * 144 * 32 + 1568 * 128 + 128 * 10)
    assert fwd == 2_436_096
    # weight grads for all four, input grads for all but conv0
    bwd = fwd + 2 * (196 * 144 * 32 + 1568 * 128 + 128 * 10)
    assert flops.train_flops_per_sample(CNN) == fwd + bwd == 7_082_496
    assert sum(flops.param_sizes(CNN)) == 206_922


def test_mclr_flops_by_hand():
    assert flops.layers(MCLR) == [(1, 60, 10)]
    # forward 1,200; the weight gradient 1,200; no input gradient
    assert flops.train_flops_per_sample(MCLR) == 2_400
    assert sorted(flops.param_sizes(MCLR)) == [10, 600]


def test_prox_bytes_and_roofline():
    # 256 stacked CNNs: every leaf x 256 is whole 128-lane rows
    f, b = flops.prox_step_cost(CNN, 256)
    assert b == 206_922 * 256 * 24 == 1_271_328_768
    assert f == 206_922 * 256 * 5
    assert flops.prox_step_roofline_s(CNN, 256, V5E) == \
        pytest.approx(1_271_328_768 / 819e9)
    # one MCLR: the bias pads to a row of 128, the weights to 5 rows
    assert flops.prox_padded_elems(10) == 128
    assert flops.prox_step_cost(MCLR, 1) == ((128 + 640) * 5,
                                             (128 + 640) * 24)
    assert flops.roofline_seconds(197e12, 1.0, V5E) == pytest.approx(1.0)


def test_peaks_table():
    p = harness.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["int8_ops"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9 and p["hbm_bytes"] == 16e9
    with pytest.raises(harness.Refused, match="not in peaks.json"):
        harness.peaks("TPU v9 imaginary")


def test_cells_are_found_by_name():
    cell = harness.find_cell("paper-cnn.train")
    assert cell.chips == 1 and cell.params["driver"] == "experiment"
    assert cell.config["kind"] == "cnn"
    assert {m["name"] for m in cell.end_to_end} == {"train_samples_per_s",
                                                   "setup_s"}
    assert "prox_roofline.train" in {m["name"] for m in cell.per_layer}
    with pytest.raises(harness.Refused, match="unknown workload"):
        harness.find_cell("no-such.cell")


def test_cpu_is_refused():
    with pytest.raises(harness.Refused, match="no accelerator"):
        harness.chips_for(harness.find_cell("paper-cnn.train"))


def test_run_without_a_chip_prints_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run = subprocess.run(
        [sys.executable, str(chipbench_path.BENCH / "run.py"), "--workload",
         "paper-cnn.train", "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, env=env, timeout=300)
    assert run.returncode != 0
    assert run.stdout == ""
    assert "no accelerator" in run.stderr
