"""The measured window of a driver whose cell calls one entry back to
back: a first call that compiles (or loads) every shape, then calls
until ``--seconds`` have passed, each inside a ``bench.call``
annotation and ended by waiting for its result. The window runs over a
frozen set-up heap, as the serve driver's does, so that the collector
does not scan it inside the window."""
from __future__ import annotations

import gc
import time

import jax


def timed_calls(ctx, call, ready):
    """Run the window. ``ready(result)`` gives the arrays a call is done
    with once ready. Returns (the last result, calls, set-up seconds,
    window seconds)."""
    res = call()
    jax.block_until_ready(ready(res))
    res = None
    ctx.mark("first call done")
    gc.collect()
    gc.freeze()
    call_s = []
    try:
        with ctx.window():
            setup_s = ctx.since_start()
            start = time.perf_counter()
            while True:
                t = time.perf_counter()
                with ctx.annotate("call"):
                    res = None                    # free the last state
                    res = call()
                    jax.block_until_ready(ready(res))
                call_s.append(time.perf_counter() - t)
                if time.perf_counter() - start >= ctx.seconds:
                    break
            window_s = time.perf_counter() - start
    finally:
        gc.unfreeze()
    ctx.mark("window of calls taking "
             + ", ".join(f"{s:.4f}" for s in call_s) + " s")
    return res, len(call_s), setup_s, window_s
