"""Device busy time per experiment call (the union of the chip's
operation intervals over the calls in the window). With MCLR's compute
negligible, this is the population-wide work of a call: store copies,
cohort gather and scatter, and the full-population eval."""


def read(tv, run, cell, peak):
    return 1e3 * tv.busy_mean_s() / run.stats["calls"]
