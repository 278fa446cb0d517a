"""Device time per batch of the serve step's tier gather and int8
decode: the operations whose scope path holds ``serve.gather``. Nothing
when no operation carries that scope."""
from chipbench import program_spans


def read(tv, run, cell, peak):
    s = program_spans.scope_seconds(tv, "serve.gather")
    return 1e3 * s / run.stats["batches"] if s > 0 else None
