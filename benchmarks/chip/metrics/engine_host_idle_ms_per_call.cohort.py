"""Device idle time per experiment call that falls inside the program's
own host spans (the engine's ``build``, ``dispatch`` and ``eval``, and
collector passes): each instant of idle goes to the innermost program or
harness span the host was in. Nothing when the program records no
spans."""
from chipbench import program_spans


def read(tv, run, cell, peak):
    if not program_spans.has_spans(tv):
        return None
    idle = program_spans.idle_seconds(tv, program_spans.PROGRAM_PREFIX)
    return 1e3 * idle / run.stats["calls"]
