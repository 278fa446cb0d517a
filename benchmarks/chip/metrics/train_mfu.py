"""Model FLOP/s utilization of training: the forward and backward
FLOPs one sample's gradient requires (from the configuration's shapes)
times the samples per second of the traced run, over the chips' bf16
peak."""
from chipbench import flops


def read(tv, run, cell, peak):
    per_sample = flops.train_flops_per_sample(cell.config)
    rate = run.stats["samples"] / run.window_s
    return 100.0 * per_sample * rate / (cell.chips * peak["bf16_flops"])
