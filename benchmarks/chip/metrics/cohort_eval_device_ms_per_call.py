"""Device time per experiment call of the population-wide eval: the
operations whose scope path holds ``engine.eval``. Nothing when no
operation carries that scope."""
from chipbench import program_spans


def read(tv, run, cell, peak):
    s = program_spans.scope_seconds(tv, "engine.eval")
    return 1e3 * s / run.stats["calls"] if s > 0 else None
