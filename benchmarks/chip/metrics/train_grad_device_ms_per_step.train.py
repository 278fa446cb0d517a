"""Device time of the model gradient per device step (one step of every
device at once): the operations whose scope path holds ``permfl.grad``,
over calls x rounds x K x L. Nothing when no operation carries that
scope."""
from chipbench import program_spans


def read(tv, run, cell, peak):
    s = program_spans.scope_seconds(tv, "permfl.grad")
    if s <= 0:
        return None
    p = cell.params
    steps = (run.stats["calls"] * p["rounds"] * p["hp"]["k_team"]
             * p["hp"]["l_local"])
    return 1e3 * s / steps
