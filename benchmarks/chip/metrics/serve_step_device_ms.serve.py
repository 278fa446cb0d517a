"""Device time of the serve step per batch: the summed duration of the
executables the chip ran in the window (``XLA Modules``; only the serve
step runs there) over the batches served."""


def read(tv, run, cell, peak):
    n = tv.module_count()
    return 1e3 * tv.module_seconds() / run.stats["batches"] if n else None
