"""Device idle time per batch that falls inside the serve call's own
host spans (``repro.serve.put``, ``.dispatch``, ``.tiers``): each
instant of idle goes to the innermost program or harness span the host
was in. Nothing when the program records no spans."""
from chipbench import program_spans


def read(tv, run, cell, peak):
    if not program_spans.has_spans(tv):
        return None
    idle = program_spans.idle_seconds(tv, "repro.serve.")
    return 1e3 * idle / run.stats["batches"]
