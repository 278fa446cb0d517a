"""Device time of the operations under the ``moe.experts`` scope (forward,
recomputation and backward) per local step: over calls x rounds x K x
L. Nothing when no operation carries that scope."""
from chipbench import program_spans


def read(tv, run, cell, peak):
    s = program_spans.scope_seconds(tv, "moe.experts")
    if s <= 0:
        return None
    return 1e3 * s / run.stats["steps"]
