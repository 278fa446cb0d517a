"""99th percentile of how late the harness handed each request to the
server after its due time (host clock): the wait in the batcher's queue,
the batch in flight included."""


def read(tv, run, cell, peak):
    return run.stats["late_p99_ms"]
