"""Share of the traced window in which no operation ran on the chip."""


def read(tv, run, cell, peak):
    return 100.0 * tv.max_idle_share()
