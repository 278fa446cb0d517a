"""Small sizes of each cell for the CPU tests, with the Pallas kernels
in interpret mode: the harness's whole path (set-up, window, reference,
comparison) runs, and no measurement is printed."""
import chipbench_path  # noqa: F401

HP = {"alpha": 0.05, "eta": 0.03, "beta": 0.6, "lam": 0.5, "gamma": 1.5,
      "k_team": 2, "l_local": 3}
TINY = {
    "paper-cnn.train": {"m": 2, "n": 3, "samples": 8, "n_val": 2, "hp": HP,
                        "rounds": 2, "eval_every": 1},
    "paper-mclr.cohort": {"n": 1000, "cohort": 16, "rounds": 3,
                          "eval_every": 3},
    "paper-cnn.serve": {"m": 2, "n": 8, "rate": 300, "pool": 64,
                        "batch_sizes": [4, 16], "sample": 64},
}
SEED = 2**31 + 11


def run(name, seconds=0.5, overrides=None):
    """One run of the tiny cell in this process (one CPU device)."""
    from chipbench import harness
    ov = dict(TINY[name], **(overrides or {}))
    return harness.run_cell(name, SEED, seconds, False, allow_cpu=True,
                            overrides=ov)

