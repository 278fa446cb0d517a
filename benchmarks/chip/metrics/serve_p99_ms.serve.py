"""99th percentile of every request's latency in the window, timed from
its due time to its batch's answers (host clock). Not an end-to-end
metric: single serve calls stall for about 125 ms in most 10-second
windows (PERF.md), which makes it bimodal from run to run."""


def read(tv, run, cell, peak):
    return run.stats["p99_ms"]
