"""Device time per batch of the serve step's vmapped forward: the
operations whose scope path holds ``serve.forward``. Nothing when no
operation carries that scope."""
from chipbench import program_spans


def read(tv, run, cell, peak):
    s = program_spans.scope_seconds(tv, "serve.forward")
    return 1e3 * s / run.stats["batches"] if s > 0 else None
