"""Model FLOP/s utilization of LoRA fine-tuning: the operations one
local step must do (``chipbench.lm_flops``: the forward of the chip's
share with the routed experts at the pairs computed, the activations'
backward and the adapters' weight gradients, nothing recomputed), times
the steps of the traced window, over its length and the chips' bf16
peak. Nothing without the run's expert counters."""
from chipbench import lm_flops


def read(tv, run, cell, peak):
    counters = run.stats.get("moe_counters")
    if not counters:
        return None
    per_step = lm_flops.step_flops(cell.config, run.stats["tokens_per_step"],
                                   cell.params["seq_len"],
                                   counters["held_pairs"])
    return (100.0 * per_step * run.stats["steps"]
            / (run.window_s * cell.chips * peak["bf16_flops"]))
