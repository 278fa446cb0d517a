"""The prox kernel's (``kernels/prox_update``) share of its roofline:
the least time its calls in the window could take, from the bytes and
operations of each call over the peaks, over the device time of the
prox ``pallas_call`` events in the trace: custom calls named after
their wrapper, ``prox_sgd_flat``. Nothing when the trace holds no such
event."""
from chipbench import flops

PATTERNS = ("%prox_sgd_flat", "custom-call(")


def read(tv, run, cell, peak):
    measured = tv.op_seconds(*PATTERNS)
    if measured <= 0:
        return None
    p = cell.params
    stacked = p["m"] * (p.get("cohort") or p["n"])
    steps = (run.stats["calls"] * p["rounds"] * p["hp"]["k_team"]
             * p["hp"]["l_local"])
    least = steps * flops.prox_step_roofline_s(cell.config, stacked, peak) * cell.chips
    return 100.0 * least / measured
