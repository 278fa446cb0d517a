"""Device time per experiment call of the cohort store's gather and
scatter: the operations whose scope path holds ``store.gather`` or
``store.scatter``. Nothing when no operation carries either scope."""
from chipbench import program_spans


def read(tv, run, cell, peak):
    s = program_spans.scope_seconds(tv, "store.gather", "store.scatter")
    return 1e3 * s / run.stats["calls"] if s > 0 else None
