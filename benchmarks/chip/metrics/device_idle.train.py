"""Share of the traced window in which no operation ran on the chip;
of four chips, the one idle the most (it waits for the slowest)."""


def read(tv, run, cell, peak):
    return 100.0 * tv.max_idle_share()
