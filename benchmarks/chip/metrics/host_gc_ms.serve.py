"""Time Python's garbage collector ran inside the window: the union of
the program's ``repro.gc.gen<N>`` spans, in milliseconds over the whole
window. Nothing when the program records no spans."""
from chipbench import program_spans


def read(tv, run, cell, peak):
    if not program_spans.has_spans(tv):
        return None
    return 1e3 * program_spans.span_seconds(tv, "repro.gc.")
